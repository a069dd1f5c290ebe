"""Milliseconds per training step that the step loop waits on the prefetch
queue for its next batch (``TrafficMeter.t_prefetch_wait``, which the
``Prefetcher`` of ``core/pipeline.py`` accumulates), over the window."""
LAYER = "pipeline"
SOURCE = "program_span"
MOVES = "train_seeds_per_s"
UNIT = "ms"


def read(rec: dict):
    if not rec.get("steps") or "prefetch_wait_s" not in rec:
        return None
    return rec["prefetch_wait_s"] / rec["steps"] * 1e3
