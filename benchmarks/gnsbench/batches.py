"""Recorded program batches turned into the reference's, with the checks
of the program's random choices on the way (see ``gnsref``).

A batch is read through its attributes only (``device.blocks``,
``input_node_ids``, ...): nothing of the program is imported.
"""
from __future__ import annotations

import numpy as np

from gnsbench.gnsref import Batch, Layer, check_lanes, device_draw


def reference_batch(cv, data: graphgen.BenchData, mb, cfg: dict,
                    backend: str, dtype=np.float64):
    """Turn one recorded batch into the reference's, checking the program's
    random choices on the way.  Returns ``(batch, bad, weight_gap, w_ref)``
    where ``w_ref`` lists the reference's lane weights of the host-drawn
    layers (for the control's own weight gap)."""
    dev = mb.device
    fan = list(cfg["fanouts"])
    n_in = int(np.asarray(dev.input_mask).sum())
    ids = np.asarray(mb.input_node_ids)[:n_in].astype(np.int64)
    nodes = ids
    bad, gap, w_all = 0, 0.0, []
    layers = []
    n_src = n_in
    for li, blk in enumerate(dev.blocks):
        d = int(np.asarray(blk.dst_mask).sum())
        idx = np.asarray(blk.nbr_idx)
        w = np.asarray(blk.nbr_w)
        bad += int((w[d:] != 0).sum()) + int(d > n_src)
        if li == 0 and backend == "device":
            bad += int((w != 0).sum())          # placeholder block
            lanes, wl, b, g, wr = _device_layer(cv, ids[:d], dev, fan[0],
                                                dtype)
            bad += b
            gap = max(gap, g)
            w_all.append(wr)
        else:
            idx, w = idx[:d], w[:d]
            live = w > 0
            bad += int((live & ((idx < 0) | (idx >= n_src))).sum())
            lanes = ids[np.clip(idx, 0, n_src - 1)]
            chk, wl = check_lanes(cv, ids[:d], lanes, w, fan[li],
                                  topup=li > 0, dtype=dtype)
            bad += chk.bad
            gap = max(gap, chk.weight_gap)
            w_all.append(wl)
        if li == 0 and backend == "device":
            loc = np.zeros(lanes.shape, dtype=np.int64)
            live = wl != 0
            nodes, loc[live] = _positions(nodes, lanes[live])
        else:
            loc = np.where(wl != 0, idx, 0)
        layers.append(Layer(num_dst=d, idx=loc,
                            w=np.asarray(wl, dtype=np.float64)))
        n_src = d
    b_real = int(np.asarray(dev.label_mask).sum())
    batch = Batch(x=data.features[nodes], layers=layers,
                  labels=data.labels[ids[:b_real]].astype(np.int32),
                  label_w=np.ones(b_real, np.float32))
    return batch, bad, gap, w_all


def _positions(nodes: np.ndarray, query: np.ndarray
               ) -> tuple[np.ndarray, np.ndarray]:
    """Positions of ``query`` ids in ``nodes`` (distinct ids), appending the
    ids it lacks."""
    new = np.setdiff1d(np.unique(query), nodes)
    nodes = np.concatenate([nodes, new])
    order = np.argsort(nodes, kind="stable")
    return nodes, order[np.searchsorted(nodes[order], query)]


def _device_layer(cv, dst, dev, k, dtype):
    """The input layer of a device-backend batch: cached destinations drawn
    by the reference's own replica of the device draw, the others from the
    host fallback lanes the batch carries (checked like host lanes)."""
    n = len(dst)
    cached = cv.in_cache[dst]
    fb_rows = np.asarray(dev.input_fb_rows)
    fb_w = np.asarray(dev.input_fb_w)
    bad = int((fb_w[:n][cached] != 0).sum()) + int((fb_w[n:] != 0).sum())
    lanes = np.zeros((n, k), dtype=np.int64)
    w = np.zeros((n, k), dtype=dtype)
    key = np.asarray(dev.sample_key)[0]
    rows = np.nonzero(cached)[0]
    lanes[rows], w[rows] = device_draw(cv, dst[rows], rows, key, k, dtype)
    unc = np.nonzero(~cached)[0]
    gap = 0.0
    wr = np.zeros((n, k), dtype=dtype)
    if len(unc):
        rows = fb_rows[unc]
        live = fb_w[unc] > 0
        bad += int((live & ((rows < 0) | (rows >= len(cv.ids)))).sum())
        fb_nodes = cv.ids[np.clip(rows, 0, len(cv.ids) - 1)]
        chk, wl = check_lanes(cv, dst[unc], fb_nodes, fb_w[unc], k,
                              topup=False, dtype=dtype)
        bad += chk.bad
        gap = chk.weight_gap
        lanes[unc], w[unc] = fb_nodes, wl
        wr[unc] = wl
    return lanes, w, bad, gap, wr
