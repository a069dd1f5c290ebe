"""Megabytes (10^6 bytes) the host-to-device copy ships per training step:
the nbytes of every leaf of the ``DeviceBatch`` handed to
``GNSEngine._put_batch``, counted from the shapes of each batch."""
LAYER = "host to device"
SOURCE = "program_counter"
MOVES = "train_seeds_per_s"
UNIT = "MB"


def read(rec: dict):
    if not rec.get("steps") or "h2d_bytes" not in rec:
        return None
    return rec["h2d_bytes"] / rec["steps"] / 1e6
