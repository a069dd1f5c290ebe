"""Readings that set a cell's limits: the program on many seeds, the
control, and planted faults, at the cell's own size, in one process.

    python3 benchmarks/gnsbench/control.py --workload <cell> \
        --seeds 11,12,13 [--serve-seconds 10] [--out readings.jsonl]

Per seed it builds the cell as a run does (``setup``; for a serving cell
also a short window at the cell's load), then prints one JSON line:

* ``program``: the numbers ``check`` compares (sound runs: lower readings);
* ``control``: the same numbers for the reference computed in bfloat16 and
  put in the program's place (the configuration states float32);
* ``half_batch`` (training): the reference with half of each batch's
  targets left out of the loss, the mean taken over the rest;
* ``frozen`` (training): the reference whose step returns its state
  unchanged.  It reads 1 on ``grad_gap`` and ``update_gap`` by definition;
  its ``loss_gap`` is measured.

Benchmark runs never run this; ``tests/test_control.py`` runs it at a
test's size on the CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np


def _bf16():
    import jax.numpy as jnp
    return jnp.bfloat16


def train_readings(driver, st) -> dict:
    from gnsbench import compare
    _, b64, bad, gap, w64 = driver.check_inputs(st, np.float64)
    _, b16, _, _, w16 = driver.check_inputs(st, _bf16())
    ref = driver.reference_outputs(st, b64)
    prog = dict(compare.train_numbers(driver.program_outputs(st), ref),
                weight_gap=gap, bad_lanes=bad)
    ctl = driver.reference_outputs(st, b16, dtype=_bf16())
    control = compare.train_numbers(ctl, ref)
    control["weight_gap"] = weight_gap(w16, w64)
    half = [dataclasses.replace(b, label_w=np.where(
        np.arange(len(b.label_w)) % 2 == 0, b.label_w, 0).astype(np.float32))
        for b in b64]
    p0 = [{k: np.asarray(v, np.float64) for k, v in p.items()}
          for p in driver.reference_params0(st)]
    frozen = {"losses": [float(st.ctx.cell.reference.loss(p0, b))
                         for b in b64],
              "grad0": [{k: np.zeros_like(v) for k, v in p.items()}
                        for p in p0],
              "params0": p0, "params": p0}
    return {"program": prog, "control": control,
            "half_batch": compare.train_numbers(
                driver.reference_outputs(st, half), ref),
            "frozen": compare.train_numbers(frozen, ref)}


def weight_gap(w_ctl: list, w_ref: list) -> float:
    """Widest relative gap between two nested lists of lane-weight arrays."""
    gap = 0.0
    for a, b in zip(w_ctl, w_ref):
        if isinstance(b, list):
            gap = max(gap, weight_gap(a, b))
            continue
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        live = b != 0
        if live.any():
            gap = max(gap, float(np.max(np.abs(a[live] - b[live])
                                        / np.abs(b[live]))))
    return gap


def serve_readings(driver, st) -> dict:
    from gnsbench import compare
    prog = driver.check(st)
    ref = st.ctx.cell.reference
    p0 = driver.reference_params0(st)
    _, pairs, _, _, w64 = driver.check_inputs(st, np.float64)
    _, pairs16, _, _, w16 = driver.check_inputs(st, _bf16())
    gap = max(compare.logit_gap(ref.logits(p0, b16, _bf16()),
                                ref.logits(p0, b64))
              for (b64, _), (b16, _) in zip(pairs, pairs16))
    return {"program": prog, "control": {
        "logit_gap": gap, "weight_gap": weight_gap(w16, w64)}}


def readings(cell, seed: int, devices, serve_seconds: float, log) -> dict:
    from gnsbench import harness
    ctx = harness.Context(cell=cell, seed=seed, devices=devices, log=log)
    t0 = time.perf_counter()
    st = cell.driver.setup(ctx)
    if cell.traffic["kind"] == "serve":
        cell.driver.measure(st, serve_seconds, False)
    cell.driver.release(st)
    out = (serve_readings if cell.traffic["kind"] == "serve"
           else train_readings)(cell.driver, st)
    out.update(seed=seed, seconds=time.perf_counter() - t0)
    return out


def main(argv=None) -> int:
    here = Path(__file__).resolve().parent
    sys.path[:0] = [str(here.parent), str(here.parents[1] / "src")]
    from gnsbench import graphgen, harness
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--serve-seconds", type=float, default=10.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    devices = harness.require_chips(cell.chips)
    cached = {}
    load = graphgen.load_data

    def load_once(cfg, *a, **kw):          # one graph for every seed
        if cfg["name"] not in cached:
            cached[cfg["name"]] = load(cfg, *a, **kw)
        return cached[cfg["name"]]

    graphgen.load_data = load_once
    for s in args.seeds.split(","):
        rec = readings(cell, int(s), devices, args.serve_seconds, harness.log)
        line = json.dumps(rec)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
