"""Fully vectorized CSR neighbor sampler (paper §3.3, hot-path rewrite).

``repro.graph.sampling.sample_blocks`` walks every destination row in a
Python loop and relabels through a dict ``pos_map`` — fine for correctness
pinning, but host sampling then dominates wall-clock and serializes against
the device step.  This module produces the *same* fixed-shape
``MinibatchBlocks`` contract with no per-row Python loops:

  * fanout draw: Floyd's algorithm over every row with ``deg > f`` at
    once, ``f`` numpy passes per layer that each draw one exact integer
    per row; a row's picks are a uniform sample without replacement from
    its neighbors, at a cost that does not grow with its degree (rows
    with ``deg <= f`` keep all neighbors in CSR order, matching the
    reference sampler).
  * relabeling: ``np.unique``/``np.setdiff1d`` for the new-leaf set and an
    ``argsort`` + ``searchsorted`` lookup instead of a Python dict.

All reference-sampler invariants are preserved (and pinned by
``tests/test_pipeline.py``): layer sizes equal ``layer_capacities``, dst
nodes are a prefix of the finer layer, halos appear only as leaves, every
sampled edge exists in the partition CSR, and at most ``fanouts[k]``
neighbors are drawn per row.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.graph.partition import Partition
from repro.graph.sampling import MinibatchBlocks, layer_capacities


def _draw_neighbors(indptr: np.ndarray, indices: np.ndarray, cur: np.ndarray,
                    num_solid: int, f: int,
                    rng: np.random.Generator,
                    allow: Optional[np.ndarray] = None) -> np.ndarray:
    """Sampled neighbor VIDs ``[len(cur), f]`` (-1 pad), no per-row loops.

    A row with ``deg <= f`` takes every neighbor in CSR order, left-packed,
    and draws nothing.  A row with ``deg > f`` takes a uniform ``f``-subset
    of its neighbors by Floyd's algorithm: for ``m = 0 .. f-1``, draw
    ``t`` uniform in ``[0, deg-f+m]`` and keep ``t``, or ``deg-f+m`` where
    ``t`` was already kept.  The loop runs over the ``f`` picks, each pass
    covering all rows.  Each call adds its active rows to the counter
    ``sample_rows{path=draw|all}``.

    ``allow`` (bool ``[len(cur)]``) suppresses expansion of individual rows:
    a row with ``allow=False`` keeps an all ``-1`` neighbor list, exactly as
    a halo does.  The serving path uses this to turn cache-resident vertices
    into leaves — their embedding is substituted from the HEC, so their
    neighborhood never needs to be materialized.
    """
    from repro import obs       # lazy: module stays importable w/o jax
    n_dst = len(cur)
    out = np.full((n_dst, f), -1, np.int64)
    valid = (cur >= 0) & (cur < num_solid)        # halos are never expanded
    if allow is not None:
        valid &= allow
    vc = np.where(valid, cur, 0)
    deg = np.where(valid, indptr[vc + 1] - indptr[vc], 0)
    # compact to rows that actually sample: wide layers are mostly padding
    act = np.flatnonzero(deg > 0)
    if f <= 0 or len(act) == 0:
        return out
    deg = deg[act]
    starts = indptr[vc[act]]

    # deg <= f rows keep every neighbor (CSR order, left-packed) — no RNG
    small = deg <= f
    if small.any():
        ds, ss = deg[small], starts[small]
        w = int(ds.max())
        col = np.arange(w)
        in_row = col[None, :] < ds[:, None]
        gi = np.minimum(ss[:, None] + col[None, :], len(indices) - 1)
        out[act[small], :w] = np.where(in_row, indices[gi], -1)

    # deg > f rows: Floyd's draw (docstring); all f picks are in-row
    big = ~small
    n_big = int(big.sum())
    obs.count("sample_rows", n_big, path="draw")
    obs.count("sample_rows", len(act) - n_big, path="all")
    if n_big:
        # in-row positions are int32, one pick per row and pass
        db = deg[big].astype(np.int32)
        sel = np.empty((f, n_big), np.int32)
        for m in range(f):
            j = db - (f - m)
            t = rng.integers(0, j + 1, dtype=np.int32)
            if m:
                np.copyto(t, j, where=(sel[:m] == t).any(0))
            sel[m] = t
        out[act[big]] = indices[starts[big][:, None] + sel.T]
    return out


def sample_blocks_vectorized(part: Partition, seeds_p: np.ndarray,
                             fanouts: Sequence[int],
                             rng: np.random.Generator,
                             batch_size: int,
                             expandable: Optional[Sequence[np.ndarray]]
                             = None) -> MinibatchBlocks:
    """Drop-in replacement for ``sample_blocks`` (same contract, >5x faster).

    The RNG consumption pattern differs from the reference sampler, so
    individual draws are not bit-identical — the sampling *distribution* is
    (uniform without replacement per row; full row when ``deg <= fanout``).

    ``expandable`` (optional, length ``L+1``; entry ``k`` a bool array over
    VID_p — covering the solids, or solids + halos for sharded serving —
    or ``None``) gates neighborhood expansion per layer: a node at
    layer ``k`` with ``expandable[k][vid] == False`` is kept as a leaf —
    its layer-``k`` embedding is expected from a cache (serving) or the HEC
    (training halos), so its subtree is never sampled.  Entry 0 is unused
    (layer 0 is never expanded).
    """
    fanouts = list(fanouts)
    L = len(fanouts)
    caps = layer_capacities(batch_size, fanouts)
    S = part.num_solid

    seeds = np.full(batch_size, -1, np.int64)
    seeds[:len(seeds_p)] = seeds_p
    seed_mask = seeds >= 0
    labels = np.zeros(batch_size, np.int64)
    labels[seed_mask] = part.labels[seeds[seed_mask]]

    layer_nodes: List[np.ndarray] = [None] * (L + 1)
    node_mask: List[np.ndarray] = [None] * (L + 1)
    nbr_idx: List[np.ndarray] = [None] * L
    layer_nodes[L] = seeds
    node_mask[L] = seed_mask

    cur = seeds
    for k in range(L - 1, -1, -1):              # seeds toward inputs
        f = fanouts[k]
        n_dst = len(cur)
        allow = None
        if expandable is not None and expandable[k + 1] is not None:
            # masks may cover solids only (single-partition serving) or
            # solids + halos (sharded serving); rows outside the mask are
            # halos or padding, which never expand regardless of `allow`
            m = expandable[k + 1]
            allow = m[np.where((cur >= 0) & (cur < len(m)), cur, 0)]
        nbrs = _draw_neighbors(part.indptr, part.indices, cur, S, f, rng,
                               allow=allow)

        # finer node list: dst prefix + sorted unique new neighbors
        flat = nbrs.ravel()
        nz = flat >= 0
        uniq = np.unique(flat[nz])
        cur_valid = cur[cur >= 0]
        extra = np.setdiff1d(uniq, cur_valid, assume_unique=True)
        cap = caps[k]
        n_fine = n_dst + len(extra)
        assert n_fine <= cap, (n_fine, cap)
        fine = np.full(cap, -1, np.int64)
        fine[:n_dst] = cur
        fine[n_dst:n_fine] = extra

        # VID_p -> position in `fine` via a direct lookup table (uninit'd is
        # fine: only positions of present VIDs are ever read back)
        vmask = fine >= 0
        fpos = np.flatnonzero(vmask)
        pos_of = np.empty(S + part.num_halo, np.int64)
        pos_of[fine[vmask]] = fpos
        positions = np.full(flat.shape, -1, np.int64)
        if nz.any():
            positions[nz] = pos_of[flat[nz]]

        nbr_idx[k] = positions.reshape(n_dst, f)
        layer_nodes[k] = fine
        node_mask[k] = vmask
        cur = fine

    return MinibatchBlocks(layer_nodes=layer_nodes, node_mask=node_mask,
                           nbr_idx=nbr_idx, seeds=seeds, seed_mask=seed_mask,
                           labels=labels)


def _segment_perms(n_seg: int, caps: Sequence[int]) -> List[np.ndarray]:
    """Per-layer node permutations fusing ``n_seg`` equal-capacity blocks
    while preserving the forward's prefix invariant (each layer's dst
    nodes are a prefix of the finer layer).

    ``perms[k][i * caps[k] + p]`` is the fused position of segment ``i``'s
    layer-``k`` node ``p``.  Layer L (seeds) is a plain concatenation;
    going finer, a dst node (``p < caps[k+1]``) tracks wherever its
    coarser copy went — the permutations compose — and the extras of all
    segments follow after every dst node."""
    L = len(caps) - 1
    perms: List[np.ndarray] = [None] * (L + 1)
    perms[L] = np.arange(n_seg * caps[L])
    for k in range(L - 1, -1, -1):
        i = np.repeat(np.arange(n_seg), caps[k])
        p = np.tile(np.arange(caps[k]), n_seg)
        dst = p < caps[k + 1]
        coarse = perms[k + 1][i * caps[k + 1] + np.minimum(p, caps[k + 1] - 1)]
        extra = caps[k] - caps[k + 1]
        perms[k] = np.where(
            dst, coarse,
            n_seg * caps[k + 1] + i * extra + (p - caps[k + 1]))
    return perms


def concat_blocks(mbs: Sequence[MinibatchBlocks]) -> MinibatchBlocks:
    """Fuse N equal-shape minibatches into ONE block-diagonal minibatch
    (multi-round exchange batching: N serve rounds run as one compiled
    step, so their per-layer halo fetches fuse into one collective pair).

    The fused graph is the disjoint union of the inputs: per layer, node
    arrays are permuted so that every coarser layer is still a prefix of
    the finer one (the invariant ``forward`` relies on for ``h[:n_dst]``),
    and ``nbr_idx`` positions are remapped through the same permutation —
    so the fused forward computes, row for row, exactly what the N
    separate forwards would."""
    if len(mbs) == 1:
        return mbs[0]
    N = len(mbs)
    L = mbs[0].num_layers
    caps = [len(x) for x in mbs[0].layer_nodes]         # per-segment caps
    assert all([len(x) for x in m.layer_nodes] == caps for m in mbs)
    perms = _segment_perms(N, caps)

    layer_nodes, node_mask, nbr_idx = [], [], []
    for k in range(L + 1):
        ln = np.concatenate([m.layer_nodes[k] for m in mbs])
        nm = np.concatenate([m.node_mask[k] for m in mbs])
        out_ln = np.empty_like(ln)
        out_nm = np.empty_like(nm)
        out_ln[perms[k]] = ln
        out_nm[perms[k]] = nm
        layer_nodes.append(out_ln)
        node_mask.append(out_nm)
    for k in range(L):
        # rows follow the (new) order of the coarser layer k+1; position
        # values are segment-local -> remap through layer k's permutation
        rows = np.concatenate(
            [np.where(m.nbr_idx[k] >= 0,
                      perms[k][i * caps[k]
                               + np.maximum(m.nbr_idx[k], 0)], -1)
             for i, m in enumerate(mbs)])
        out = np.empty_like(rows)
        out[perms[k + 1]] = rows
        nbr_idx.append(out)
    return MinibatchBlocks(
        layer_nodes=layer_nodes, node_mask=node_mask, nbr_idx=nbr_idx,
        seeds=np.concatenate([m.seeds for m in mbs]),
        seed_mask=np.concatenate([m.seed_mask for m in mbs]),
        labels=np.concatenate([m.labels for m in mbs]))


def stack_ranks(mbs: Sequence[MinibatchBlocks]) -> Dict:
    """Stack per-rank blocks into the host-side [R, ...] minibatch layout.

    Kept as numpy so prefetch workers never touch jax; ``staging`` owns
    the host->device transfer.
    """
    L = mbs[0].num_layers
    return {
        "seeds": np.stack([m.seeds for m in mbs]).astype(np.int32),
        "seed_mask": np.stack([m.seed_mask for m in mbs]),
        "labels": np.stack([m.labels for m in mbs]).astype(np.int32),
        "nbr_idx": [np.stack([m.nbr_idx[k] for m in mbs]).astype(np.int32)
                    for k in range(L)],
        "layer_nodes": [np.stack([m.layer_nodes[k] for m in mbs])
                        .astype(np.int32) for k in range(L + 1)],
        "node_mask": [np.stack([m.node_mask[k] for m in mbs])
                      for k in range(L + 1)],
    }
