"""The device sampler's Pallas gather (``sampling/kernels.py`` ->
``kernels/gather_agg.py``) against its roofline: the least time its
operations and bytes over the window's steps take at the chip's peaks
(``counts.gather_cost`` over the padded ``[rows, k0]`` lane block), over
the summed device time of the Pallas kernel in the trace."""
from gnsbench import counts

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "train_seeds_per_s"
UNIT = "%"


def read(rec: dict):
    tr = rec.get("trace")
    per_step = rec.get("gather_per_step")
    if tr is None or per_step is None or tr.kernel_s <= 0:
        return None
    flops, nbytes = (x * rec["steps"] for x in per_step)
    t, _bound = counts.roofline_s(flops, nbytes, rec["peaks"])
    return 100.0 * t / tr.kernel_s
