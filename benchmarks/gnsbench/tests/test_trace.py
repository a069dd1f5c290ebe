"""The trace-to-metrics reduction, on hand-made events and on a small
trace recorded on a TPU v5e (``data/tpu_trace.json``: the device operations
and benchmark spans of a short traced window, kept as JSON)."""
import json
from pathlib import Path

import pytest

from gnsbench import trace

DATA = Path(__file__).resolve().parent / "data"
OP = '%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop'
KERNEL = ('%closed_call.4 = f32[6553,1,100]{2,1,0} custom-call(s32[32765]{0}'
          ' %a), custom_call_target="tpu_custom_call"')


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_innermost_segments():
    spans = [(0, 10, "outer", "t"), (2, 4, "inner", "t")]
    assert trace.innermost_segments(spans) == [
        (0, 2, "outer"), (2, 4, "inner"), (4, 10, "outer")]


def test_op_label():
    assert trace.op_label(OP) == "fusion"
    assert trace.op_label(KERNEL) == "pallas:closed_call"


def test_reduce_by_hand():
    # window 0..100 ns; ops busy 10-30 and 25-40 (union 30 ns) and a kernel
    # 60-70 (10 ns): busy 40 ns, idle 60 ns.  Device gaps 0-10 and 40-60
    # fall in "h2d" spans, 70-100 only in a background span.
    tr = trace.Trace(
        device_ops={"/device:TPU:0": [(10, 30, OP), (25, 40, OP),
                                      (60, 70, KERNEL), (150, 160, OP)]},
        spans=[(0, 100, trace.WINDOW, "python"),
               (0, 12, "gnsbench.h2d", "python"),
               (38, 65, "gnsbench.step", "python"),
               (39, 64, "gnsbench.h2d", "python"),
               (0, 100, "gnsbench.bg.sample", "python")])
    r = trace.reduce(tr)
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(40e-9)
    assert r.kernel_s == pytest.approx(10e-9)
    assert r.device_ops[0] == ["fusion", pytest.approx(35e-9)]
    assert dict((k, v) for k, v in r.idle_gaps) == {
        "gnsbench.h2d": pytest.approx(30e-9),
        "no span": pytest.approx(30e-9)}


def test_reduce_needs_one_window_and_some_device_work():
    with pytest.raises(ValueError):
        trace.reduce(trace.Trace(device_ops={}, spans=[]))
    with pytest.raises(ValueError):
        trace.reduce(trace.Trace(device_ops={}, spans=[
            (0, 1, trace.WINDOW, "main")]))


def test_load_reads_benchmark_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    trace.start(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        with jax.profiler.TraceAnnotation("gnsbench.step"):
            jnp.ones(8).sum().block_until_ready()
        with jax.profiler.TraceAnnotation("not_ours"):
            pass
    trace.stop()
    tr = trace.load(str(tmp_path))
    names = sorted(s[2] for s in tr.spans)
    assert names == ["gnsbench.step", trace.WINDOW]
    lo, hi = tr.window()
    step = [s for s in tr.spans if s[2] == "gnsbench.step"][0]
    assert lo <= step[0] <= step[1] <= hi


def test_recorded_tpu_trace():
    rec = json.loads((DATA / "tpu_trace.json").read_text())
    tr = trace.Trace(device_ops={k: [tuple(e) for e in v]
                                 for k, v in rec["device_ops"].items()},
                     spans=[tuple(s) for s in rec["spans"]])
    r = trace.reduce(tr)
    want = rec["expected"]
    assert r.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert r.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert r.kernel_s == pytest.approx(want["kernel_s"], rel=1e-9)
    assert 0 < r.busy_s < r.window_s
    assert r.kernel_s > 0
