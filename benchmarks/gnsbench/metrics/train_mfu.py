"""The whole training step's share of the chip's bf16 peak: the FLOPs the
forward and backward passes require on the real (unpadded) rows of every
step in the window (the configured reference's ``train_flops``), over the
traced window times the chips times the peak."""
LAYER = "compiled step"
SOURCE = "device_trace"
MOVES = "train_seeds_per_s"
UNIT = "%"


def read(rec: dict):
    tr = rec.get("trace")
    if tr is None or not rec.get("flops") or tr.window_s <= 0:
        return None
    peak = rec["peaks"]["bf16_flops"] * rec["chips"]
    return 100.0 * rec["flops"] / (tr.window_s * peak)
