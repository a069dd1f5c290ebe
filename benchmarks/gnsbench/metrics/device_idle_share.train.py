"""Share of the traced training window in which no operation ran on the
device: 1 - (union of XLA op intervals) / window, averaged over chips."""
LAYER = "device"
SOURCE = "device_trace"
MOVES = "train_seeds_per_s"
UNIT = "%"


def read(rec: dict):
    tr = rec.get("trace")
    if tr is None or "steps" not in rec or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
