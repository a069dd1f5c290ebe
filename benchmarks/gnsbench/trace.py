"""Profiler trace capture and its reduction to device metrics.

A traced run wraps its measured window in the span ``gnsbench.window`` and
the benchmark's calls into each layer in ``gnsbench.<layer>`` spans
(``jax.profiler.TraceAnnotation``), so host spans and device operations
share one clock.  :func:`load` keeps, from the ``.xplane.pb`` the profiler
writes:

* per TPU device plane, the ``XLA Ops`` line (one event per operation that
  ran on the device);
* from the host plane, only the ``gnsbench.*`` spans, with their line.

:func:`reduce` turns that into the numbers the result line carries: busy
seconds (the union of operation intervals inside the window, averaged over
the chips used), the window's length, summed kernel time, and the
``breakdown`` (top device operations, idle gaps by what the host did).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Optional

WINDOW = "gnsbench.window"
SPAN_PREFIX = "gnsbench."
BACKGROUND = "gnsbench.bg."     # spans of threads that do not drive the device
PALLAS_MARK = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass
class Trace:
    """Events as ``(start_ns, end_ns, name)``; host spans also carry the
    name of their host-plane line."""
    device_ops: dict           # device plane name -> list of events
    spans: list                # (start_ns, end_ns, name, thread)

    def window(self) -> tuple[float, float]:
        w = [s for s in self.spans if s[2] == WINDOW]
        if len(w) != 1:
            raise ValueError(f"trace holds {len(w)} {WINDOW} spans, not 1")
        return w[0][0], w[0][1]


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # Python call tracing slows the host
    opts.host_tracer_level = 1        # user spans only, not the runtime's
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def load(log_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    import jax
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = jax.profiler.ProfileData.from_file(max(files, key=os.path.getmtime))
    device_ops, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device_ops[plane.name] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name, line.name))
    return Trace(device_ops=device_ops, spans=spans)


def _clip(events, lo, hi):
    for s, e, name in events:
        s2, e2 = max(s, lo), min(e, hi)
        if e2 > s2:
            yield s2, e2, name


def union(intervals) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` pairs into disjoint sorted intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def op_label(name: str) -> str:
    """A stable short name for an HLO operation event: the instruction name
    without its ``%`` and numeric suffix, tagged when it is a Pallas
    kernel."""
    lhs = name.split(" = ", 1)[0].strip().lstrip("%")
    lhs = re.sub(r"(\.\d+)+$", "", lhs)
    return f"pallas:{lhs}" if PALLAS_MARK in name else lhs


def innermost_segments(spans) -> list[tuple[float, float, str]]:
    """Cut the timeline at every span boundary; each piece is labelled with
    the shortest span that covers it (the innermost, for nested spans)."""
    bounds = sorted({t for s in spans for t in (s[0], s[1])})
    out = []
    active: list = []
    by_start = sorted(spans, key=lambda s: s[0])
    j = 0
    for a, b in zip(bounds, bounds[1:]):
        while j < len(by_start) and by_start[j][0] <= a:
            active.append(by_start[j])
            j += 1
        active = [s for s in active if s[1] > a]
        if active:
            out.append((a, b, min(active, key=lambda s: s[1] - s[0])[2]))
    return out


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float              # mean over the devices used
    kernel_s: float            # Pallas kernel time, mean over devices
    device_ops: list           # [[label, seconds]] top 10, mean over devices
    idle_gaps: list            # [[host activity, seconds]] top 10, device 0
    n_devices: int


def reduce(tr: Trace, devices: Optional[list] = None) -> Reduction:
    """Reduce one traced window.

    ``devices`` names the device planes to average over (default: all
    that ran an operation).  Idle gaps of the first device are attributed
    to the innermost ``gnsbench`` span that covers the gap's midpoint,
    leaving out background spans (``gnsbench.bg.*``: threads that feed
    the device without driving it, such as the prefetch sampler); a gap no
    span covers is ``"no span"``.
    """
    lo, hi = tr.window()
    if devices is None:
        devices = sorted(d for d, ev in tr.device_ops.items() if ev)
    if not devices:
        raise ValueError("no device operation was traced")
    busy, kern, per_op = [], [], {}
    for d in devices:
        ev = list(_clip(tr.device_ops.get(d, []), lo, hi))
        busy.append(sum(e - s for s, e in union((s, e) for s, e, _ in ev)))
        kern.append(sum(e - s for s, e, n in ev if PALLAS_MARK in n))
        for s, e, n in ev:
            lab = op_label(n)
            per_op[lab] = per_op.get(lab, 0.0) + (e - s)
    nd = len(devices)
    ops = sorted(([k, v / nd * 1e-9] for k, v in per_op.items()),
                 key=lambda kv: -kv[1])[:10]

    cand = [s for s in tr.spans
            if s[2] != WINDOW and not s[2].startswith(BACKGROUND)]
    segs = innermost_segments(cand)
    starts = [g[0] for g in segs]
    first = union((s, e) for s, e, _ in _clip(tr.device_ops[devices[0]],
                                              lo, hi))
    edges = [lo] + [x for iv in first for x in iv] + [hi]
    gaps: dict = {}
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        mid = 0.5 * (s + e)
        i = bisect.bisect_right(starts, mid) - 1
        lab = segs[i][2] if i >= 0 and segs[i][1] > mid else "no span"
        gaps[lab] = gaps.get(lab, 0.0) + (e - s)
    idle = sorted(([k, v * 1e-9] for k, v in gaps.items()),
                  key=lambda kv: -kv[1])[:10]
    return Reduction(window_s=(hi - lo) * 1e-9, busy_s=sum(busy) / nd * 1e-9,
                     kernel_s=sum(kern) / nd * 1e-9, device_ops=ops,
                     idle_gaps=idle, n_devices=nd)
