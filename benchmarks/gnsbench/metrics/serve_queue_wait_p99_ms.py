"""99th percentile of the time a served request waited in the fabric's
queues before its batch started (``ServeMeter``'s queue-wait split, timed
from submit, over its rolling window of the latest requests)."""
LAYER = "serving"
SOURCE = "program_span"
MOVES = "serve_p99_ms"
UNIT = "ms"


def read(rec: dict):
    return rec.get("queue_wait_p99_ms")
