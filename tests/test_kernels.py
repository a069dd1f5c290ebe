"""Per-kernel allclose vs pure-jnp oracles, interpret=True on CPU.

Sweeps shapes/dtypes per the deliverable spec; hypothesis drives randomized
index/weight patterns for gather_agg.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                          # bare env: seeded fallback shim
    from _hypothesis_fallback import given, settings, st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.gather_agg import gather_agg_pallas
from repro.kernels.ops import flash_attention, gather_agg


# ---------------------------------------------------------------------------
# gather_agg
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,d,b,k", [
    (32, 16, 8, 4),
    (128, 64, 16, 8),
    (1000, 128, 32, 15),
    (64, 96, 7, 5),       # d not a power of two
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_agg_matches_ref(n, d, b, k, dtype):
    rng = np.random.default_rng(0)
    feat = jnp.asarray(rng.normal(size=(n, d)), dtype)
    idx = jnp.asarray(rng.integers(0, n, (b, k)), jnp.int32)
    w = jnp.asarray(rng.random((b, k)), jnp.float32)
    out = gather_agg_pallas(feat, idx, w, block_d=min(d, 64), interpret=True)
    expect = ref.gather_agg_ref(feat, idx, w)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=tol, atol=tol)


def test_gather_agg_zero_weight_lanes_ignore_index():
    """Padded lanes (w=0) must not contribute, whatever their index."""
    feat = jnp.asarray(np.full((10, 8), 1e30), jnp.float32)
    idx = jnp.zeros((4, 3), jnp.int32)
    w = jnp.zeros((4, 3), jnp.float32)
    out = gather_agg_pallas(feat, idx, w, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


@given(
    n=st.integers(4, 200),
    b=st.integers(1, 16),
    k=st.integers(1, 12),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=25, deadline=None)
def test_gather_agg_property(n, b, k, seed):
    rng = np.random.default_rng(seed)
    d = int(rng.choice([8, 16, 32]))
    feat = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n, (b, k)), jnp.int32)
    w = jnp.asarray(rng.normal(size=(b, k)), jnp.float32)
    out = gather_agg_pallas(feat, idx, w, block_d=d, interpret=True)
    expect = ref.gather_agg_ref(feat, idx, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)


def test_gather_agg_ops_wrapper_dispatch():
    rng = np.random.default_rng(1)
    feat = jnp.asarray(rng.normal(size=(50, 24)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 50, (6, 4)), jnp.int32)
    w = jnp.asarray(rng.random((6, 4)), jnp.float32)
    out_k = gather_agg(feat, idx, w, impl="pallas")
    out_r = gather_agg(feat, idx, w, impl="reference")
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kernel", ["gather_agg", "slot_gather",
                                    "cache_lookup"])
def test_lane_chunks_bitwise_parity(kernel, monkeypatch):
    """SMEM-sized lane chunks (several pallas_calls under lax.map, k-major
    lanes, a padded tail chunk) must not change a single bit."""
    from repro.kernels import gather_agg as ga
    from repro.kernels.cache_lookup import cache_lookup_agg_pallas
    from repro.sampling.kernels import slot_gather_agg_pallas
    from repro.sampling.ref import slot_gather_agg_ref

    monkeypatch.setattr(ga, "SMEM_LANES", 12)     # 3 rows x K=4 per chunk
    assert ga.lane_chunks(11, 4) == (3, 4)
    rng = np.random.default_rng(0)
    feat = jnp.asarray(rng.integers(-8, 9, (40, 16)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 40, (11, 4)), jnp.int32)
    w = jnp.asarray(rng.integers(-4, 5, (11, 4)), jnp.float32)
    if kernel == "gather_agg":
        out = gather_agg_pallas(feat, idx, w, interpret=True)
        expect = ref.gather_agg_ref(feat, idx, w)
    elif kernel == "slot_gather":
        lanes = jnp.where(idx % 3 == 0, -1, idx)         # some dead lanes
        out = slot_gather_agg_pallas(feat, lanes, w, interpret=True)
        expect = slot_gather_agg_ref(feat, lanes, w)
    else:
        streamed = jnp.asarray(rng.integers(-8, 9, (40, 16)), jnp.float32)
        slots = jnp.asarray(np.where(np.arange(40) % 2 == 0,
                                     np.arange(40) % 17, -1), jnp.int32)
        out = cache_lookup_agg_pallas(feat[:17], streamed, slots, idx, w,
                                      interpret=True)
        expect = ref.cache_lookup_agg_ref(feat[:17], streamed, slots, idx, w)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

def _rand_qkv(rng, b, hq, hkv, sq, sk, dh, dtype=jnp.float32):
    q = jnp.asarray(rng.normal(size=(b, hq, sq, dh)), dtype)
    k = jnp.asarray(rng.normal(size=(b, hkv, sk, dh)), dtype)
    v = jnp.asarray(rng.normal(size=(b, hkv, sk, dh)), dtype)
    return q, k, v


@pytest.mark.parametrize("b,hq,hkv,s,dh,blk", [
    (1, 2, 2, 64, 32, 16),     # MHA
    (2, 4, 2, 128, 64, 32),    # GQA group=2
    (1, 8, 1, 64, 64, 16),     # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_causal_matches_ref(b, hq, hkv, s, dh, blk, dtype):
    rng = np.random.default_rng(0)
    q, k, v = _rand_qkv(rng, b, hq, hkv, s, s, dh, dtype)
    out = flash_attention_pallas(q, k, v, causal=True, block_q=blk,
                                 block_k=blk, interpret=True)
    expect = ref.mha_ref(q, k, v, causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


def test_flash_sliding_window():
    rng = np.random.default_rng(1)
    q, k, v = _rand_qkv(rng, 1, 2, 2, 128, 128, 32)
    out = flash_attention_pallas(q, k, v, causal=True, window=32,
                                 block_q=32, block_k=32, interpret=True)
    expect = ref.mha_ref(q, k, v, causal=True, window=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_flash_cross_attention_no_causal():
    rng = np.random.default_rng(2)
    q, k, v = _rand_qkv(rng, 2, 2, 2, 32, 96, 32)
    out = flash_attention_pallas(q, k, v, causal=False, block_q=16,
                                 block_k=32, interpret=True)
    expect = ref.mha_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_flash_decode_one_token_against_cache():
    """Sq=1 decode against a longer KV cache, end-aligned positions."""
    rng = np.random.default_rng(3)
    q, k, v = _rand_qkv(rng, 1, 4, 2, 1, 256, 64)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=64)
    expect = ref.mha_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_flash_kv_len_masks_padding():
    """Keys beyond kv_len must be invisible."""
    rng = np.random.default_rng(4)
    q, k, v = _rand_qkv(rng, 1, 2, 2, 32, 64, 32)
    # poison the padded tail
    k = k.at[:, :, 48:, :].set(1e5)
    v = v.at[:, :, 48:, :].set(1e5)
    out = flash_attention_pallas(q, k, v, causal=False, kv_len=48,
                                 q_offset=48 - 32, block_q=16, block_k=16,
                                 interpret=True)
    expect = ref.mha_ref(q, k[:, :, :48], v[:, :, :48], causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_flash_ops_wrapper_pads_odd_lengths():
    rng = np.random.default_rng(5)
    q, k, v = _rand_qkv(rng, 1, 2, 1, 37, 53, 32)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    expect = ref.mha_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# kernel wired into the model
# ---------------------------------------------------------------------------

def test_graphsage_pallas_impl_matches_reference():
    import dataclasses
    from repro.core.sampler import SamplerConfig, make_sampler
    from repro.graph.datasets import get_dataset
    from repro.models import graphsage

    ds = get_dataset("tiny", seed=0)
    cfg = SamplerConfig(fanouts=(3, 4, 5), batch_size=8)
    s = make_sampler("ns", ds.graph, cfg, ds.features, ds.labels)
    rng = np.random.default_rng(0)
    s.start_epoch(0, rng)
    mb = s.sample(rng.choice(ds.train_idx, 8, replace=False).astype(np.int64), rng)

    mcfg = graphsage.SageConfig(feat_dim=ds.feat_dim, hidden_dim=16,
                                num_classes=ds.num_classes)
    params = graphsage.init_params(jax.random.PRNGKey(0), mcfg)
    table = graphsage.dummy_cache_table(ds.feat_dim)
    ref_logits = graphsage.forward(params, mb.device, table, mcfg)
    pal_cfg = dataclasses.replace(mcfg, aggregate_impl="pallas")
    pal_logits = graphsage.forward(params, mb.device, table, pal_cfg)
    np.testing.assert_allclose(np.asarray(pal_logits), np.asarray(ref_logits),
                               rtol=1e-4, atol=1e-4)
