"""Graph substrate tests: CSR, generators, partitioning."""
import numpy as np
import pytest

from repro.graph.csr import CSRGraph
from repro.graph.generate import powerlaw_graph, sbm_graph, node_features_from_labels
from repro.graph.datasets import get_dataset
from repro.graph.partition import hash_partition


def test_csr_from_edges_basic():
    src = np.array([0, 1, 2, 2])
    dst = np.array([1, 2, 0, 3])
    g = CSRGraph.from_edges(src, dst, 4)
    assert g.num_nodes == 4
    # symmetrized + deduped
    assert set(g.neighbors(2).tolist()) == {0, 1, 3}
    assert set(g.neighbors(0).tolist()) == {1, 2}
    assert g.degrees.sum() == g.num_edges


def test_csr_no_self_loops():
    g = CSRGraph.from_edges(np.array([0, 1, 1]), np.array([0, 1, 2]), 3)
    for v in range(3):
        assert v not in g.neighbors(v)


def test_powerlaw_degree_tail():
    g = powerlaw_graph(20_000, avg_degree=10, seed=1)
    deg = g.degrees
    assert 5 <= deg.mean() <= 20
    # heavy tail: max degree far above mean
    assert deg.max() > 10 * deg.mean()


def test_sample_neighbors_small_degree_full():
    g = CSRGraph.from_edges(np.array([0, 0]), np.array([1, 2]), 4)
    rng = np.random.default_rng(0)
    nbrs, mask = g.sample_neighbors(np.array([0, 3]), k=5, rng=rng)
    assert mask[0].sum() == 2 and set(nbrs[0][mask[0]].tolist()) == {1, 2}
    assert mask[1].sum() == 0  # isolated node


def test_sample_neighbors_no_replacement():
    # star: node 0 connected to 1..20
    src = np.zeros(20, dtype=np.int64)
    dst = np.arange(1, 21)
    g = CSRGraph.from_edges(src, dst, 21)
    rng = np.random.default_rng(0)
    for _ in range(10):
        nbrs, mask = g.sample_neighbors(np.array([0]), k=10, rng=rng)
        picked = nbrs[0][mask[0]]
        assert len(picked) == 10
        assert len(np.unique(picked)) == 10  # distinct


def test_sample_neighbors_uniformity():
    src = np.zeros(8, dtype=np.int64)
    dst = np.arange(1, 9)
    g = CSRGraph.from_edges(src, dst, 9)
    rng = np.random.default_rng(0)
    counts = np.zeros(9)
    for _ in range(2000):
        nbrs, mask = g.sample_neighbors(np.array([0]), k=2, rng=rng)
        for x in nbrs[0][mask[0]]:
            counts[x] += 1
    freq = counts[1:] / counts[1:].sum()
    assert np.allclose(freq, 1 / 8, atol=0.02)


def _regular_rows(rows: int, deg: int) -> CSRGraph:
    """``rows`` nodes of degree ``deg``; node r's neighbours are the next
    ``deg`` ids after r (mod rows), so offset i of row r is (r + 1 + i)."""
    indptr = np.arange(rows + 1, dtype=np.int64) * deg
    r = np.repeat(np.arange(rows), deg)
    indices = ((r + 1 + np.tile(np.arange(deg), rows)) % rows).astype(np.int32)
    return CSRGraph(indptr=indptr, indices=indices)


_DRAW_CASES = [(k, deg) for k in (5, 10, 15) for deg in (k + 1, 2 * k, 20 * k)]
_DRAW_ROWS = 20_000


def _draw_offsets(k: int, deg: int, seed: int) -> np.ndarray:
    """One vectorized draw over many rows of degree ``deg``, mapped back to
    neighbour offsets ``[0, deg)`` (int64 [rows, k])."""
    g = _regular_rows(_DRAW_ROWS, deg)
    nodes = np.arange(_DRAW_ROWS, dtype=np.int64)
    nbrs, mask = g.sample_neighbors(nodes, k, np.random.default_rng(seed))
    assert mask.all()
    return (nbrs.astype(np.int64) - nodes[:, None] - 1) % _DRAW_ROWS


@pytest.mark.parametrize("k,deg", _DRAW_CASES)
def test_sample_neighbors_draw_distinct_in_list(k, deg):
    # one call over rows of mixed degree: isolated, below, at and above k
    degs = np.array([0, 1, k - 1, k, deg] * 400)
    indptr = np.concatenate([[0], np.cumsum(degs)]).astype(np.int64)
    n = len(degs)
    row = np.repeat(np.arange(n), degs)
    pos = np.arange(indptr[-1]) - indptr[row]
    g = CSRGraph(indptr=indptr,
                 indices=((row + 1 + pos) % n).astype(np.int32))
    nodes = np.arange(n, dtype=np.int64)
    nbrs, mask = g.sample_neighbors(nodes, k, np.random.default_rng(k * deg))
    assert nbrs.shape == mask.shape == (n, k)
    assert nbrs.dtype == np.int32 and mask.dtype == bool
    np.testing.assert_array_equal(mask.sum(axis=1), np.minimum(degs, k))
    assert (nbrs[~mask] == 0).all()
    offs = (nbrs.astype(np.int64) - nodes[:, None] - 1) % n
    assert (offs[mask] < np.broadcast_to(degs[:, None], mask.shape)[mask]).all()
    srt = np.sort(np.where(mask, offs, -1 - np.arange(k)), axis=1)
    assert (srt[:, 1:] != srt[:, :-1]).all()        # no lane repeats


@pytest.mark.parametrize("k,deg", _DRAW_CASES)
def test_sample_neighbors_inclusion_frequency(k, deg):
    """Every neighbour is drawn with probability k/deg."""
    offs = _draw_offsets(k, deg, seed=deg + 1)
    counts = np.bincount(offs.ravel(), minlength=deg)
    p = k / deg
    z = (counts - _DRAW_ROWS * p) / np.sqrt(_DRAW_ROWS * p * (1 - p))
    assert np.abs(z).max() < 5, z


@pytest.mark.parametrize("k,deg", _DRAW_CASES)
def test_sample_neighbors_lanes_exchangeable(k, deg):
    """Lane 0 and lane k-1 are each uniform over the neighbours: callers
    keep lanes by position, so no lane may favour any part of the list."""
    from scipy.stats import chisquare
    offs = _draw_offsets(k, deg, seed=2 * deg + 3)
    for lane in (0, k - 1):
        counts = np.bincount(offs[:, lane], minlength=deg)
        assert chisquare(counts).pvalue > 1e-4, (lane, counts)


def test_induced_cache_adjacency():
    g = powerlaw_graph(2000, avg_degree=8, seed=2)
    rng = np.random.default_rng(0)
    cache_mask = rng.random(2000) < 0.1
    s = g.induced_cache_adjacency(cache_mask)
    assert s.num_nodes == g.num_nodes
    for v in rng.integers(0, 2000, size=50):
        expected = sorted(u for u in g.neighbors(v) if cache_mask[u])
        assert sorted(s.neighbors(v).tolist()) == expected


def test_sbm_homophily():
    g, labels = sbm_graph(5000, num_blocks=8, avg_degree=10, p_in=0.8, seed=3)
    src = np.repeat(np.arange(g.num_nodes), g.degrees)
    same = (labels[src] == labels[g.indices]).mean()
    assert same > 0.5  # strongly assortative vs 1/8 baseline


def test_features_class_separated():
    labels = np.random.default_rng(0).integers(0, 4, size=1000).astype(np.int32)
    x = node_features_from_labels(labels, 16, noise=0.1, seed=0)
    # class means well separated at low noise
    mus = np.stack([x[labels == c].mean(0) for c in range(4)])
    d = np.linalg.norm(mus[0] - mus[1])
    assert d > 1.0


def test_dataset_splits_disjoint():
    ds = get_dataset("tiny", seed=0)
    all_idx = np.concatenate([ds.train_idx, ds.val_idx, ds.test_idx])
    assert len(np.unique(all_idx)) == len(all_idx)
    assert ds.features.shape == (ds.graph.num_nodes, 32)


def test_hash_partition_covers_graph():
    g = powerlaw_graph(3000, avg_degree=6, seed=4)
    parts = hash_partition(g, 4)
    total_owned = sum(p.num_owned for p in parts)
    assert total_owned == g.num_nodes
    # per-part CSR matches global rows
    p = parts[1]
    for i in [0, 5, len(p.owned) - 1]:
        v = p.owned[i]
        local = p.local_indices[p.local_indptr[i]:p.local_indptr[i + 1]]
        np.testing.assert_array_equal(local, g.neighbors(v))
