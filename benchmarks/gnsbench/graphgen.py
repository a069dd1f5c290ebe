"""The benchmark's own graph generator, disk cache and dataset builder.

A copy of the program's power-law degree-corrected stochastic block model
(``repro.graph.generate.sbm_graph``), kept here so that no later change to
the program can move the yardstick.  The random draws are made in the same
order as the original; the CSR is built with a sort and a ``bincount``
instead of ``np.unique`` and ``np.add.at``, which gives the same arrays in
a fraction of the time at millions of nodes.

The graph is part of a configuration: it is drawn from ``graph_seed`` in
the configuration file, never from a run's ``--seed``.  The first run in a
checkout writes it under ``.data/`` beside this file (ignored by git) and
later runs load it.  Features are drawn anew in every run (one bulk
``float32`` draw, cheaper than reading them back from disk).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from pathlib import Path
from typing import Optional

import numpy as np

DATA_DIR = Path(__file__).resolve().parent / ".data"
# Raise when the generator's output changes, so stale caches are not read.
GENERATOR_VERSION = 1
GRAPH_KEYS = ("num_nodes", "avg_degree", "num_classes", "alpha", "p_in",
              "graph_seed")


def powerlaw_degrees(n: int, avg_deg: float, alpha: float,
                     rng: np.random.Generator) -> np.ndarray:
    """Zipf(alpha) degree sequence scaled to ``avg_deg``, hubs capped at
    max(sqrt(n), 20·avg_deg), re-centred after the cap."""
    u = rng.random(n)
    raw = u ** (-1.0 / (alpha - 1.0))
    deg = raw * (avg_deg / raw.mean())
    cap = max(float(n) ** 0.5, 20.0 * avg_deg)
    deg = np.minimum(deg, cap)
    deg = deg * (avg_deg / max(deg.mean(), 1e-9))
    return np.maximum(deg.astype(np.int64), 1)


def csr_from_edges(src: np.ndarray, dst: np.ndarray, n: int
                   ) -> tuple[np.ndarray, np.ndarray]:
    """Undirected CSR: both directions, self-loops dropped, duplicates
    removed, neighbours sorted.  Returns ``(indptr int64, indices int32)``."""
    m = len(src)
    keys = np.empty(2 * m, dtype=np.int64)
    np.multiply(src, n, out=keys[:m])
    keys[:m] += dst
    np.multiply(dst, n, out=keys[m:])
    keys[m:] += src
    keys = keys[np.concatenate([src != dst, src != dst])]
    keys.sort()
    if len(keys):
        keep = np.empty(len(keys), dtype=bool)
        keep[0] = True
        np.not_equal(keys[1:], keys[:-1], out=keep[1:])
        keys = keys[keep]
    rows = keys // n
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(rows, minlength=n), out=indptr[1:])
    del rows
    indices = (keys % n).astype(np.int32)
    return indptr, indices


def sbm_graph(num_nodes: int, num_blocks: int, avg_degree: float,
              p_in: float = 0.8, alpha: float = 2.1, seed: int = 0
              ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Power-law degree-corrected SBM: ``(indptr, indices, labels)``.

    Stubs are paired at random; a pair that crosses blocks is rewired with
    probability ``p_in`` to a degree-biased node of the source's block, so
    labels correlate with neighbourhoods.
    """
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, num_blocks, size=num_nodes)
    deg = powerlaw_degrees(num_nodes, avg_degree, alpha, rng)
    stubs = np.repeat(np.arange(num_nodes, dtype=np.int64), deg)
    rng.shuffle(stubs)
    if len(stubs) % 2:
        stubs = stubs[:-1]
    src, dst = stubs[0::2].copy(), stubs[1::2].copy()
    cross = labels[src] != labels[dst]
    rewire = cross & (rng.random(len(src)) < p_in)
    if rewire.any():
        order = np.argsort(labels[stubs], kind="stable")
        sorted_stubs = stubs[order]
        del order
        block_of_sorted = labels[sorted_stubs]
        starts = np.searchsorted(block_of_sorted, np.arange(num_blocks))
        ends = np.searchsorted(block_of_sorted, np.arange(num_blocks),
                               side="right")
        del block_of_sorted
        b = labels[src[rewire]]
        lo, hi = starts[b], ends[b]
        pick = lo + (rng.random(len(b)) * np.maximum(hi - lo, 1)).astype(
            np.int64)
        dst[rewire] = sorted_stubs[np.minimum(pick, len(sorted_stubs) - 1)]
    del stubs
    indptr, indices = csr_from_edges(src, dst, num_nodes)
    return indptr, indices, labels.astype(np.int32)


def graph_key(cfg: dict) -> str:
    blob = json.dumps({k: cfg[k] for k in GRAPH_KEYS} | {
        "generator": GENERATOR_VERSION}, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def load_graph(cfg: dict, data_dir: Optional[Path] = None, log=print
               ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The configuration's graph, from the disk cache or generated once."""
    d = (data_dir or DATA_DIR) / f"{cfg['name']}-{graph_key(cfg)}"
    names = ("indptr", "indices", "labels")
    if all((d / f"{x}.npy").is_file() for x in names):
        return tuple(np.load(d / f"{x}.npy") for x in names)
    t0 = time.perf_counter()
    arrays = sbm_graph(cfg["num_nodes"], cfg["num_classes"],
                       cfg["avg_degree"], p_in=cfg["p_in"],
                       alpha=cfg["alpha"], seed=cfg["graph_seed"])
    tmp = d.with_name(d.name + f".tmp{os.getpid()}")
    tmp.mkdir(parents=True, exist_ok=True)
    for x, a in zip(names, arrays):
        np.save(tmp / f"{x}.npy", a)
    if d.exists():        # another run finished first: keep its copy
        for f in tmp.iterdir():
            f.unlink()
        tmp.rmdir()
    else:
        os.replace(tmp, d)
    nbytes = sum(int(a.nbytes) for a in arrays)
    log(f"graph {cfg['name']}: generated in {time.perf_counter() - t0:.1f}s,"
        f" {len(arrays[1])} CSR entries, {nbytes} bytes cached at {d}")
    return arrays


def node_features(labels: np.ndarray, num_classes: int, feat_dim: int,
                  noise: float, seed: int) -> np.ndarray:
    """Class-prototype features ``proto[y] + noise·N(0, I)``, float32."""
    rng = np.random.default_rng(seed)
    protos = rng.standard_normal((num_classes, feat_dim), dtype=np.float32)
    x = rng.standard_normal((len(labels), feat_dim), dtype=np.float32)
    x *= np.float32(noise)
    x += protos[labels]
    return x


@dataclasses.dataclass
class BenchData:
    """Everything the configuration fixes about the data, as host arrays."""
    indptr: np.ndarray
    indices: np.ndarray
    labels: np.ndarray
    features: np.ndarray
    train_idx: np.ndarray
    val_idx: np.ndarray
    test_idx: np.ndarray
    num_classes: int

    @property
    def num_nodes(self) -> int:
        return len(self.indptr) - 1


def load_data(cfg: dict, data_dir: Optional[Path] = None, log=print
              ) -> BenchData:
    indptr, indices, labels = load_graph(cfg, data_dir, log)
    n = len(labels)
    feats = node_features(labels, cfg["num_classes"], cfg["feat_dim"],
                          cfg["feature_noise"], cfg["graph_seed"] + 1)
    perm = np.random.default_rng(cfg["graph_seed"] + 2).permutation(n)
    n_tr = int(n * cfg["train_frac"])
    n_va = max(int(n * cfg["val_frac"]), 1)
    return BenchData(indptr=indptr, indices=indices, labels=labels,
                     features=feats, train_idx=np.sort(perm[:n_tr]),
                     val_idx=np.sort(perm[n_tr:n_tr + n_va]),
                     test_idx=np.sort(perm[n_tr + n_va:]),
                     num_classes=cfg["num_classes"])


def as_program_dataset(data: BenchData, name: str):
    """Hand the data to the system under test as its ``GraphDataset``."""
    from repro.graph.csr import CSRGraph
    from repro.graph.datasets import GraphDataset
    return GraphDataset(
        name=name, graph=CSRGraph(indptr=data.indptr, indices=data.indices),
        features=data.features, labels=data.labels,
        train_idx=data.train_idx, val_idx=data.val_idx,
        test_idx=data.test_idx, num_classes=data.num_classes)
