"""One run of one cell: find it by name, load, warm up, measure, check.

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json``  — sizes, source, what was cut and assumed;
* ``traffic/<traffic>.json`` — the job or request mix; its ``kind`` names
  the driver;
* ``drivers/<kind>.py``      — ``setup``, ``measure``, ``release``, ``check``;
* ``references/<name>.py``   — the plain reference a configuration names;
* ``metrics/<metric>.py``    — one reader per per-layer metric;
* ``limits/<cell>.json``     — the limit of each number ``check`` compares.

A configuration selects its architecture with two keys: ``reference``
names the reference module, and ``engine_model`` (optional) holds the
fields of the program's ``ModelConfig`` besides ``hidden_dim`` and the
traffic's ``input_impl`` (``engine_model.model_config``).  The reference
module is the architecture's contract with the harness; the drivers call
nothing else of it:

* ``init_params(seed, cfg)`` — the parameters before the first step, a
  list of one dict of arrays per layer, in the layout the program keeps
  under ``params["layers"]``;
* ``loss(params, batch, dtype)`` — the mean loss over a ``gnsref.Batch``;
* ``train(params0, batches, opt, dtype)`` — Adam over the batches
  (``gnsref.train`` on the architecture's loss): the step losses, the first
  gradient and the parameters after the last step;
* ``logits(params, batch, dtype)`` — the output rows of a batch's targets;
* ``train_flops(real_dst, cfg)`` — the FLOPs one training step requires,
  from the real destination rows of each layer (what ``train_mfu`` reads).

The GNS sampling replica every reference shares, and the batch format,
live in ``gnsref``.

A run: refuse unless JAX finds the cell's TPU chips; ``setup`` (data, the
system under test, compiles, the first steps the check follows); measure
for ``--seconds`` with tracing off (end-to-end metrics) or on (per-layer
metrics); read the peak device memory; free the program's state; compare
against the reference; print the numbers compared beside their limits as
the last lines of standard error, and the result as the last line of
standard output.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
TRACE_DIR = HERE / ".cache" / "trace"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# What the drivers call of a configuration's reference module.
REFERENCE_CONTRACT = ("init_params", "loss", "train", "logits", "train_flops")


class Refused(RuntimeError):
    """The run cannot be made here (no chip, unknown cell)."""


def load_module(path: Path, name: str):
    if not path.is_file():
        raise Refused(f"{path} does not exist")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _for_cell(entries: list, cell: str) -> list:
    return [e for e in entries if "workloads" not in e or cell in e["workloads"]]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    driver: object
    reference: object
    limits: dict
    end_to_end: list             # BENCHMARK.json entries for this cell
    per_layer: list
    readers: dict                # metric name -> reader module


def load_cell(name: str, root: Path = ROOT, here: Path = HERE,
              bench: Optional[dict] = None) -> Cell:
    """The cell ``name`` of ``bench`` (default: the checkout's
    ``BENCHMARK.json``), with every file it names loaded."""
    if bench is None:
        bench = json.loads((root / "BENCHMARK.json").read_text())
    wl = [w for w in bench["workloads"] if w["name"] == name]
    if len(wl) != 1:
        raise Refused(f"no workload named {name!r} in BENCHMARK.json")
    wl = wl[0]
    cfg_entry = [c for c in bench["configs"] if c["name"] == wl["config"]][0]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads((here / "traffic" / f"{wl['traffic']}.json")
                         .read_text())
    per_layer = _for_cell(bench["per_layer"], name)
    return Cell(
        name=name, chips=int(wl["chips"]), config=config, traffic=traffic,
        driver=load_module(here / "drivers" / f"{traffic['kind']}.py",
                           f"gnsbench_driver_{traffic['kind']}"),
        reference=load_module(here / "references" / f"{config['reference']}.py",
                              f"gnsbench_reference_{config['reference']}"),
        limits=json.loads((here / "limits" / f"{name}.json").read_text()),
        end_to_end=_for_cell(bench["end_to_end"], name),
        per_layer=per_layer,
        readers={m["name"]: load_module(
            here / "metrics" / f"{m['name']}.py",
            "gnsbench_metric_" + m["name"].replace(".", "_"))
            for m in per_layer})


def require_chips(n: int) -> list:
    """The first ``n`` TPU devices, or :class:`Refused`."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"JAX finds no TPU (platform {devs[0].platform!r}); "
                      "this benchmark measures the chip only")
    if len(devs) < n:
        raise Refused(f"the cell needs {n} TPU chips and JAX finds {len(devs)}")
    from gnsbench import peaks
    try:
        peaks.peaks_for(devs[0].device_kind)
    except KeyError as e:
        raise Refused(str(e)) from None
    return devs[:n]


class CompileClock:
    """Backend compiles (persistent-cache reads included) and cache hits,
    counted from ``jax.monitoring`` events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def span(name: str):
    """A ``gnsbench.<name>`` host span in the profiler's trace."""
    import jax
    return jax.profiler.TraceAnnotation(f"gnsbench.{name}")


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell, the run's seed and its devices."""
    cell: Cell
    seed: int
    devices: list
    log: object = log
    span: object = span

    @property
    def engine_seed(self) -> int:
        """The seed handed to the program (its PRNG keys hold 31 bits)."""
        return self.seed % (2 ** 31)

    def window(self):
        return span("window")


@dataclasses.dataclass
class Measured:
    """A driver's measured window."""
    end_to_end: dict            # metric name -> value
    attempted: int
    failed: int
    record: dict                # what per-layer readers read


def peak_bytes(devices: list) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run(cell: Cell, seed: int, seconds: float, traced: bool,
        t_start: float, devices: list) -> tuple[dict, dict]:
    """One run; returns the result line and ``{check: (value, limit)}``."""
    import jax
    from gnsbench import peaks, trace
    clock = CompileClock()
    ctx = Context(cell=cell, seed=seed, devices=devices)
    state = cell.driver.setup(ctx)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.2f}s: {clock.compiles} compiles "
        f"({clock.seconds:.2f}s), {clock.cache_hits} persistent-cache hits")
    n_compiles = clock.compiles
    tdir = TRACE_DIR / cell.name
    if traced:
        shutil.rmtree(tdir, ignore_errors=True)
        trace.start(str(tdir))
    try:
        m = cell.driver.measure(state, seconds, traced)
    finally:
        if traced:
            trace.stop()
    if clock.compiles != n_compiles:
        log(f"WARNING: {clock.compiles - n_compiles} compiles inside the "
            "measured window")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": peak_bytes(devices)}
    result: dict = {}
    if traced:
        red = trace.reduce(trace.load(str(tdir)),
                           devices=[f"/device:TPU:{d.id}" for d in devices])
        shutil.rmtree(tdir, ignore_errors=True)
        m.record.update(trace=red, peaks=peaks.peaks_for(devices[0].device_kind),
                        chips=len(devices))
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        metrics = {}
        for entry in cell.per_layer:
            v = cell.readers[entry["name"]].read(m.record)
            if v is not None:
                metrics[entry["name"]] = {"value": v, "unit": entry["unit"]}
        result["breakdown"] = {"device_ops": red.device_ops,
                               "idle_gaps": red.idle_gaps}
    else:
        values = dict(m.end_to_end, setup_s=setup_s)
        metrics = {e["name"]: {"value": values[e["name"]], "unit": e["unit"]}
                   for e in cell.end_to_end}
    cell.driver.release(state)
    gc.collect()
    t_check = time.perf_counter()
    numbers = cell.driver.check(state)
    log(f"check took {time.perf_counter() - t_check:.2f}s")
    checks = {k: (float(numbers[k]), float(cell.limits[k]))
              for k in cell.limits}
    correct = all(v <= lim for v, lim in checks.values())
    out = {"correct": bool(correct), "attempted": int(m.attempted),
           "failed": int(m.failed), "metrics": metrics, "device": device}
    out.update(result)
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out, checks


def main(argv: Optional[list] = None, t_start: Optional[float] = None) -> int:
    if t_start is None:
        t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    try:
        cell = load_cell(args.workload)
        import jax
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        devices = require_chips(cell.chips)
    except Refused as e:
        log(f"refused: {e}")
        return 3
    out, checks = run(cell, args.seed, args.seconds, bool(args.trace),
                      t_start, devices)
    for k, (v, lim) in checks.items():
        log(f"check {k} {v!r} limit {lim!r} {'ok' if v <= lim else 'FAIL'}")
    print(json.dumps(out), flush=True)
    return 0
