"""A run with the timed path broken underneath comes out not correct.

Each test skips only the harness's look for a chip: the cell's own driver,
reference and limits run at a test's size on the CPU, with one fault
planted in the program the window drives.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.gns.engine as engine_mod
from gnsbench import harness

TRAIN_CELLS = ["products_train", "papers_train_device"]


def run(cell, cpu, seed=1234567890123):
    out, checks = harness.run(cell, seed, 1.0, False, time.perf_counter(), cpu)
    return out, checks


@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_sound_run_is_correct(tiny_cell, cpu, name):
    out, checks = run(tiny_cell(name), cpu)
    assert out["correct"], checks
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_state_unchanged_fails(tiny_cell, cpu, monkeypatch, name):
    make = engine_mod.make_train_step

    def frozen(mcfg, opt):
        step = make(mcfg, opt)

        def train_step(params, opt_state, *a, **kw):
            _, _, loss, acc = step(params, opt_state, *a, **kw)
            return params, opt_state, loss, acc
        return train_step
    monkeypatch.setattr(engine_mod, "make_train_step", frozen)
    out, checks = run(tiny_cell(name), cpu)
    assert not out["correct"]
    assert checks["update_gap"][0] > checks["update_gap"][1]


@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_half_batch_fails(tiny_cell, cpu, monkeypatch, name):
    make = engine_mod.make_train_step

    def halved(mcfg, opt):
        step = make(mcfg, opt)

        def train_step(params, opt_state, batch, *a, **kw):
            keep = (jnp.arange(batch.label_mask.shape[0]) % 2 == 0)
            batch.label_mask = batch.label_mask * keep
            return step(params, opt_state, batch, *a, **kw)
        return train_step
    monkeypatch.setattr(engine_mod, "make_train_step", halved)
    out, checks = run(tiny_cell(name), cpu)
    assert not out["correct"]
    assert any(checks[k][0] > checks[k][1]
               for k in ("loss_gap", "grad_gap", "update_gap"))


def test_device_draw_altered_fails(tiny_cell, cpu, monkeypatch):
    """The device draw is checked through the loss: a draw keyed off the
    batch's key reads a different sample."""
    import repro.sampling.kernels as sk
    draw = sk.draw_lanes

    def shifted(adj, dst_rows, keys, k, num_groups=1):
        return draw(adj, dst_rows, keys + jnp.uint32(1), k, num_groups)
    monkeypatch.setattr(sk, "draw_lanes", shifted)
    jax.clear_caches()              # the jitted op traced the real draw
    try:
        out, checks = run(tiny_cell("papers_train_device"), cpu)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not out["correct"]
    assert any(checks[k][0] > checks[k][1]
               for k in ("loss_gap", "grad_gap", "update_gap"))


def test_serve_sound_run_is_correct(tiny_cell, cpu):
    out, checks = run(tiny_cell("products_serve"), cpu)
    assert out["correct"], checks
    assert out["failed"] == 0


def test_serve_answer_altered_fails(tiny_cell, cpu, monkeypatch):
    compute = engine_mod.GNSEngine.infer_compute

    def altered(self, mb, meter=None):
        out = np.array(compute(self, mb, meter))
        out[0, 0] += 0.25
        return out
    monkeypatch.setattr(engine_mod.GNSEngine, "infer_compute", altered)
    out, checks = run(tiny_cell("products_serve"), cpu)
    assert not out["correct"]
    assert checks["logit_gap"][0] > checks["logit_gap"][1]


def test_serve_answer_misrouted_fails(tiny_cell, cpu, monkeypatch):
    """An answer altered after the step, where the fabric slices it for its
    request, reads as a mismatch."""
    from repro.serve import fabric
    serve_batch = fabric.FabricWorker._serve_batch

    def misrouted(self, live, t_start):
        for p in live:
            fut = p.future
            done = fut._complete

            def complete(res, done=done):
                if res.logits is not None:
                    res.logits = res.logits[::-1].copy() + 1.0
                done(res)
            fut._complete = complete
        return serve_batch(self, live, t_start)
    monkeypatch.setattr(fabric.FabricWorker, "_serve_batch", misrouted)
    out, checks = run(tiny_cell("products_serve"), cpu)
    assert not out["correct"]
    assert checks["answer_mismatch"][0] > 0
