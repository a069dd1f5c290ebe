"""The operation and byte counters against hand counts, and the FLOP total
a training run takes from its reference."""
import dataclasses
import types

import numpy as np
import pytest

from gnsbench import counts, harness, peaks
from repro.core.minibatch import DeviceBatch, LayerBlock, block_pad_sizes


def shaped_batch(batch, fanouts, feat, device=False):
    """A DeviceBatch of zeros with the padded shapes the samplers ship."""
    pads = block_pad_sizes(batch, fanouts)
    if device:                              # the input block is a placeholder
        pads[0] = (pads[0][0], pads[0][0])
    blocks = []
    for li, (d, s) in enumerate(pads):
        k = 1 if (device and li == 0) else fanouts[li]
        blocks.append(LayerBlock(np.zeros((d, k), np.int32),
                                 np.zeros((d, k), np.float32),
                                 np.zeros(d, np.float32), s, d))
    s0 = pads[0][1]
    extra = {}
    if device:
        extra = dict(input_fb_rows=np.zeros((s0, fanouts[0]), np.int32),
                     input_fb_w=np.zeros((s0, fanouts[0]), np.float32),
                     sample_key=np.zeros((1, 2), np.uint32))
    return DeviceBatch(blocks=tuple(blocks),
                       input_cache_slots=np.zeros(s0, np.int32),
                       input_streamed=np.zeros((s0, feat), np.float32),
                       input_mask=np.zeros(s0, np.float32),
                       labels=np.zeros(batch, np.int32),
                       label_mask=np.zeros(batch, np.float32), **extra)


def test_products_host_batch_is_440_07_mb():
    # input rows 1000*16*11*6 = 1,056,000: features 422.4 MB, slots and
    # mask 4.224 MB each; blocks 7.744 + 1.344 + 0.124 MB; labels 8 kB
    b = shaped_batch(1000, [5, 10, 15], 100)
    assert counts.tree_nbytes(b) == 440_068_000


def test_papers_device_batch_is_102_15_mb():
    # 176,000 input rows of 128 features (90.112 MB), slots, mask and the
    # placeholder block (5 x 0.704 MB), fallback lanes 7.04 MB, upper
    # blocks 1.468 MB, labels 8 kB, the 8-byte draw key
    b = shaped_batch(1000, [5, 10, 15], 128, device=True)
    assert counts.tree_nbytes(b) == 102_148_008


def test_train_flops_hand_count():
    # two layers, 3 -> 4 -> 2 features, 10 and 5 real destination rows,
    # fanouts 2 and 3.  Input layer: agg 2*10*2*3 = 120, linear
    # 2*10*6*4 = 480, counted as 120 + 2*480.  Layer 1: agg 2*5*3*4 = 120,
    # linear 2*5*8*2 = 160, counted three times.
    got = counts.sage_train_flops([10, 5], [2, 3], 3, 4, 2)
    assert got == 120 + 2 * 480 + 3 * (120 + 160)


def test_paper_step_flops():
    # the paper's step on real rows of a products batch is tens of GFLOP
    f = counts.sage_train_flops([59_000, 12_000, 1_000], [5, 10, 15],
                                100, 256, 47)
    assert 1e10 < f < 1e11


def test_gather_cost_and_roofline():
    flops, nbytes = counts.gather_cost(176_000, 5, 128)
    assert flops == 2 * 176_000 * 5 * 128
    assert nbytes == 176_000 * 5 * 128 * 4 + 176_000 * 128 * 4 \
        + 176_000 * 5 * 8
    t, bound = counts.roofline_s(flops, nbytes, peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory"
    assert t == pytest.approx(nbytes / 819e9)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")


def counting(ref):
    """The contract's functions of ``ref``, with its ``init_params`` and
    ``train_flops`` calls recorded."""
    calls = {"init_params": 0, "train_flops": []}
    mod = types.SimpleNamespace(**{fn: getattr(ref, fn)
                                   for fn in harness.REFERENCE_CONTRACT})

    def init_params(seed, cfg):
        calls["init_params"] += 1
        return ref.init_params(seed, cfg)

    def train_flops(real_dst, cfg):
        flops = ref.train_flops(real_dst, cfg)
        calls["train_flops"].append((list(real_dst), flops))
        return flops
    mod.init_params, mod.train_flops = init_params, train_flops
    return mod, calls


@pytest.mark.parametrize("name", ["products_train", "papers_train_device"])
def test_flops_come_from_the_reference(tiny_cell, cpu, name):
    cell = tiny_cell(name)
    ref, calls = counting(cell.reference)
    cell = dataclasses.replace(cell, reference=ref)
    ctx = harness.Context(cell=cell, seed=2718281828, devices=cpu,
                          log=lambda msg: None)
    st = cell.driver.setup(ctx)
    warm = len(calls["train_flops"])
    assert warm == cell.traffic["warmup_steps"]
    m = cell.driver.measure(st, 1.0, False)
    window = [f for _, f in calls["train_flops"][warm:]]
    assert len(window) == m.record["steps"] > 0
    assert m.record["flops"] == sum(window)
    cfg = cell.config
    for real_dst, flops in calls["train_flops"]:     # graphsage's own count
        assert flops == counts.sage_train_flops(
            real_dst, cfg["fanouts"], cfg["feat_dim"],
            cfg["hidden_dim"], cfg["num_classes"])
    cell.driver.release(st)
    nums = cell.driver.check(st)
    assert calls["init_params"] == 1
    assert all(nums[k] <= lim for k, lim in cell.limits.items()), nums
