"""Training cells: ``GNSEngine.fit`` with the prefetch pipeline on.

Set-up builds one engine and drives it, through ``fit`` itself, over its
first ``warmup_steps`` steps: they compile the step, fill the pipeline,
give the rate that sizes the window, and the first ``check_steps`` of them
are the ones the reference follows.  The window is one more ``fit`` call
whose step count is sized to last about ``--seconds``; no cache refresh
falls inside it (the number of epochs stays within the refresh period).

The benchmark wraps three of the engine's methods on the instance, to put
its spans on the trace and to count what each step shipped: ``run_batch``
(one optimizer step), ``_put_batch`` (the host-to-device copy) and the
sampler's ``sample`` (host sampling, on the prefetch thread).
"""
from __future__ import annotations

import dataclasses
import math
import time

import numpy as np

from gnsbench import batches, compare, counts, engine_model, gnsref, graphgen


@dataclasses.dataclass
class Recorder:
    """Per-step counts, and the first steps' state for the check."""
    capture: int = 0                   # steps whose batch and state to keep
    steps: int = 0
    seeds: int = 0
    h2d_bytes: int = 0
    flops: float = 0.0
    t_end: list = dataclasses.field(default_factory=list)
    losses: list = dataclasses.field(default_factory=list)
    batches: list = dataclasses.field(default_factory=list)
    m1: object = None                  # Adam's first moment after step 1
    params3: object = None             # parameters after the last kept step

    def reset(self) -> None:
        self.capture = self.steps = self.seeds = self.h2d_bytes = 0
        self.flops = 0.0
        self.t_end = []


@dataclasses.dataclass
class State:
    ctx: object
    data: graphgen.BenchData
    eng: object
    rec: Recorder
    params0: object
    cache_ids: np.ndarray
    rate: float                        # steps per second after warm-up
    steps_per_epoch: int


def _engine_config(cfg: dict, tr: dict, seed: int):
    from repro.core.sampler import SamplerConfig
    from repro.featurestore import CacheConfig
    from repro.gns import EngineConfig
    from repro.optim.adam import AdamConfig
    return EngineConfig(
        sampler="gns",
        sampling=SamplerConfig(batch_size=cfg["batch_size"],
                               fanouts=tuple(cfg["fanouts"]),
                               backend=tr["sampler_backend"]),
        cache=CacheConfig(fraction=cfg["cache_fraction"],
                          period=cfg["cache_period"],
                          strategy=cfg["cache_policy"]),
        model=engine_model.model_config(cfg, input_impl=tr["input_impl"]),
        optim=AdamConfig(lr=cfg["lr"], b1=cfg["adam_b1"], b2=cfg["adam_b2"],
                         eps=cfg["adam_eps"]),
        seed=seed, prefetch=True)


def _real_dst(mb) -> list:
    return [int(np.asarray(b.dst_mask).sum()) for b in mb.device.blocks]


def instrument(eng, rec: Recorder, span, cfg: dict, ref) -> None:
    """Wrap the engine's step, copy and sampling calls (module doc); each
    step's FLOPs are the reference ``ref``'s count."""
    import jax
    run_batch, put, sample = eng.run_batch, eng._put_batch, eng.sampler.sample

    def step(mb, home_shards=None):
        with span("step"):
            out = run_batch(mb, home_shards)
        rec.t_end.append(time.perf_counter())
        rec.steps += 1
        rec.seeds += int(np.asarray(mb.device.label_mask).sum())
        rec.h2d_bytes += counts.tree_nbytes(mb.device)
        rec.flops += ref.train_flops(_real_dst(mb), cfg)
        if rec.steps <= rec.capture:
            rec.batches.append(mb)
            rec.losses.append(float(out[0]))
            if rec.steps == 1:
                rec.m1 = jax.device_get(eng.opt_state["m"])
            if rec.steps == rec.capture:
                rec.params3 = jax.device_get(eng.params)
        return out

    def put_batch(host_batch, meter=None):
        with span("h2d"):
            return put(host_batch, meter)

    def sample_batch(targets, rng):
        with span("bg.sample"):
            return sample(targets, rng)

    eng.run_batch = step
    eng._put_batch = put_batch
    eng.sampler.sample = sample_batch


def setup(ctx) -> State:
    import jax
    from repro.gns import GNSEngine
    cfg, tr = ctx.cell.config, ctx.cell.traffic
    data = graphgen.load_data(cfg, log=ctx.log)
    t0 = time.perf_counter()
    eng = GNSEngine(_engine_config(cfg, tr, ctx.engine_seed),
                    dataset=graphgen.as_program_dataset(data, cfg["name"]))
    ctx.log(f"engine built in {time.perf_counter() - t0:.2f}s")
    rec = Recorder(capture=tr["check_steps"])
    instrument(eng, rec, ctx.span, cfg, ctx.cell.reference)
    params0 = jax.device_get(eng.params)
    warm = tr["warmup_steps"]
    t0 = time.perf_counter()
    eng.fit(epochs=1, max_batches=warm, prefetch=True)
    ctx.log(f"warm-up: {rec.steps} steps in {time.perf_counter() - t0:.2f}s,"
            f" losses {rec.losses}")
    assert rec.steps == warm, (rec.steps, warm)
    tail = tr["rate_steps"]
    rate = tail / (rec.t_end[-1] - rec.t_end[-1 - tail])
    spe = len(data.train_idx) // cfg["batch_size"]
    cache_ids = np.array(eng.store.generation.state.node_ids, copy=True)
    return State(ctx=ctx, data=data, eng=eng, rec=rec, params0=params0,
                 cache_ids=cache_ids, rate=rate, steps_per_epoch=spe)


def window_plan(steps: int, per_epoch: int, period: int) -> tuple[int, int]:
    """``(epochs, max_batches)`` for about ``steps`` steps of ``fit`` with no
    cache refresh due: at most ``period`` epochs (epoch 0's generation is
    already live)."""
    epochs = min(max(math.ceil(steps / per_epoch), 1), period)
    return epochs, min(max(math.ceil(steps / epochs), 1), per_epoch)


def measure(st: State, seconds: float, traced: bool):
    from gnsbench.harness import Measured
    cfg = st.ctx.cell.config
    eng, rec = st.eng, st.rec
    epochs, per = window_plan(round(seconds * st.rate), st.steps_per_epoch,
                              cfg["cache_period"])
    rec.reset()
    wait0 = eng.meter.t_prefetch_wait
    with st.ctx.window():
        t0 = time.perf_counter()
        eng.fit(epochs=epochs, max_batches=per, prefetch=True)
        wall = time.perf_counter() - t0
    st.ctx.log(f"window: {rec.steps} steps ({epochs} x {per}) in {wall:.3f}s"
               f" (warm-up rate {st.rate:.3f} steps/s)")
    tr = st.ctx.cell.traffic
    k0 = cfg["fanouts"][0]
    d0 = np.asarray(st.rec.batches[0].device.blocks[0].dst_mask).shape[0]
    gather = (counts.gather_cost(d0, k0, cfg["feat_dim"])
              if tr["sampler_backend"] == "device" else None)
    record = {"steps": rec.steps, "seeds": rec.seeds, "wall_s": wall,
              "h2d_bytes": rec.h2d_bytes, "flops": rec.flops,
              "prefetch_wait_s": eng.meter.t_prefetch_wait - wait0,
              "gather_per_step": gather}
    return Measured(end_to_end={"train_seeds_per_s": rec.seeds / wall},
                    attempted=rec.steps, failed=0, record=record)


def release(st: State) -> None:
    """Drop the engine (device arrays, threads' references) before the
    reference runs; keep what the check needs."""
    st.eng = None


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def check_inputs(st: State, dtype=np.float64):
    """The cache view, the reference batches and the sampler checks."""
    cfg, data = st.ctx.cell.config, st.data
    size = int(data.num_nodes * cfg["cache_fraction"])
    ids = st.cache_ids
    bad = int(len(ids) != size or len(np.unique(ids)) != len(ids)
              or ids.min() < 0 or ids.max() >= data.num_nodes)
    cv = gnsref.CacheView(data.indptr, data.indices, ids, size)
    backend = st.ctx.cell.traffic["sampler_backend"]
    out, gap, w_all = [], 0.0, []
    for mb in st.rec.batches:
        b, nb, g, wl = batches.reference_batch(cv, data, mb, cfg, backend,
                                               dtype)
        out.append(b)
        bad += nb
        gap = max(gap, g)
        w_all.append(wl)
    return cv, out, bad, gap, w_all


def reference_params0(st: State):
    return st.ctx.cell.reference.init_params(st.ctx.engine_seed,
                                             st.ctx.cell.config)


def program_outputs(st: State) -> dict:
    cfg = st.ctx.cell.config
    lay = lambda p: p["layers"]
    m1 = lay(st.rec.m1)
    grad0 = [{k: np.asarray(v, np.float64) / (1.0 - cfg["adam_b1"])
              for k, v in p.items()} for p in m1]
    return {"losses": st.rec.losses, "grad0": grad0,
            "params0": lay(st.params0), "params": lay(st.rec.params3)}


def reference_outputs(st: State, ref_batches: list, dtype=None) -> dict:
    import jax.numpy as jnp
    cfg = st.ctx.cell.config
    ref = st.ctx.cell.reference
    p0 = reference_params0(st)
    opt = {"lr": cfg["lr"], "b1": cfg["adam_b1"], "b2": cfg["adam_b2"],
           "eps": cfg["adam_eps"]}
    out = ref.train(p0, ref_batches, opt, dtype or jnp.float32)
    out["params0"] = [{k: np.asarray(v, np.float64) for k, v in p.items()}
                      for p in p0]
    return out


def check(st: State) -> dict:
    _, ref_batches, bad, gap, _ = check_inputs(st)
    nums = compare.train_numbers(program_outputs(st),
                                 reference_outputs(st, ref_batches))
    return dict(nums, weight_gap=gap, bad_lanes=bad)
