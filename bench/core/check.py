"""The comparison that decides ``correct``.

The program's first three training steps (recorded by the warm-up epoch
through the window's own compiled step and feed) are set against the
plain reference (``reference.py``) following the same minibatches from
its own weights.  Numbers compared, each against its limit from the
cell's ``workloads/<cell>.json``:

* ``loss_gap``    the largest relative gap of a step's loss.
* ``grad_gap``    the first step's clipped gradient, as Adam got it
                  (first moment / (1 - b1)), by the worst leaf: the gap of
                  the two norms over the larger of the reference's norm of
                  that leaf and of the median leaf.
* ``grad_err``    the same gradient, by the worst leaf: the norm of the
                  difference of the two over the same scale.  The gaps of
                  norms cannot see rounding that is as often up as down;
                  this number does, so it is the one that rejects the
                  control (bfloat16 activations and gradients).
* ``change_gap``  the gap of norms for the weights' change over the three
                  steps, leaving out leaves whose reference gradient is
                  under a thousandth of the median leaf's (they move by
                  round-off).
* ``batch_faults`` sampled edges that are not edges of the benchmark's
                  graph, seeds that are no training vertex of their rank
                  or come twice, labels that are not the graph's, dst rows
                  that are not the finer layer's prefix (limit 0).
* ``push_mismatch`` (several chips) the share of HEC lines, after the last
                  recorded step, that the program and the reference's own
                  push do not both hold.
* ``push_gap``    (several chips) the largest relative gap of a line both
                  hold, against the row its owner computed in the
                  reference.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from core import reference as ref_lib

B1 = 0.9


# ---------------------------------------------------------------------------
# the recorded minibatches, in global ids, and their faults
# ---------------------------------------------------------------------------
def rank_batches(mbs: List[dict], seeds: List[int], ps, graph,
                 num_layers: int) -> List[List[ref_lib.RankBatch]]:
    """Per step, per rank: the recorded minibatch in global vertex ids;
    labels are the benchmark graph's own."""
    out = []
    for mb, seed in zip(mbs, seeds):
        row = []
        for r, part in enumerate(ps.parts):
            to_o = part.vid_p_to_o()
            S = part.num_solid
            vids, solid = [], []
            for k in range(num_layers + 1):
                n = np.asarray(mb["layer_nodes"][k][r]).astype(np.int64)
                vids.append(np.where(n >= 0, to_o[np.maximum(n, 0)], -1))
                solid.append((n >= 0) & (n < S))
            sm = np.asarray(mb["seed_mask"][r])
            labels = np.where(sm, graph.labels[np.maximum(vids[-1], 0)], 0)
            row.append(ref_lib.RankBatch(
                vids=vids, solid=solid,
                nbr_idx=[np.asarray(x[r]) for x in mb["nbr_idx"]],
                labels=labels.astype(np.int32), seed_mask=sm, seed=seed))
        out.append(row)
    return out


def batch_faults(steps: List[List[ref_lib.RankBatch]], mbs: List[dict],
                 graph, fanouts) -> int:
    """Count of broken minibatch invariants (see module doc)."""
    faults = 0
    seen = []
    for batches, mb in zip(steps, mbs):
        for r, b in enumerate(batches):
            L = len(b.nbr_idx)
            for k in range(L):
                nbr = b.nbr_idx[k]
                n_dst = nbr.shape[0]
                faults += int(nbr.shape[1] != fanouts[k])
                faults += int((b.vids[k][:n_dst] != b.vids[k + 1]).sum())
                rows, cols = np.nonzero(nbr >= 0)
                dst = b.vids[k + 1][rows]
                src = b.vids[k][nbr[rows, cols]]
                faults += int(((dst < 0) | ~b.solid[k + 1][rows]).sum())
                ok = (dst >= 0) & (src >= 0)
                faults += int((~ok).sum())
                faults += int((~graph.has_edges(dst[ok], src[ok])).sum())
                picked = np.where(nbr >= 0, b.vids[k][np.maximum(nbr, 0)], -1)
                picked = np.sort(picked, axis=1)
                faults += int(((picked[:, 1:] == picked[:, :-1])
                               & (picked[:, 1:] >= 0)).sum())
            s = b.vids[-1][b.seed_mask]
            faults += int((~b.solid[-1][b.seed_mask]).sum())
            faults += int((~graph.train_mask[np.maximum(s, 0)]).sum())
            prog_labels = np.asarray(mb["labels"][r])[b.seed_mask]
            faults += int((prog_labels != b.labels[b.seed_mask]).sum())
            seen.append(s)
    seen = np.concatenate(seen) if seen else np.empty(0, np.int64)
    faults += len(seen) - len(np.unique(seen))
    return faults


def owes_table(ps) -> List[np.ndarray]:
    """Per rank r: [R, V] bool, vertex v is r's and a halo on peer j."""
    R = ps.num_parts
    V = len(ps.owner)
    out = []
    for r in range(R):
        t = np.zeros((R, V), bool)
        for j, pj in enumerate(ps.parts):
            if j != r:
                h = pj.halo_vids[pj.halo_owner == r]
                t[j, h] = True
        out.append(t)
    return out


# ---------------------------------------------------------------------------
# the numbers
# ---------------------------------------------------------------------------
def _leaf_norms(tree) -> Dict[str, float]:
    import jax
    return {jax.tree_util.keystr(p): float(np.linalg.norm(
        np.asarray(x, np.float64)))
        for p, x in jax.tree_util.tree_leaves_with_path(tree)}


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   keep: Optional[List[str]] = None):
    """max over leaves |prog - ref| / max(ref, median ref leaf)."""
    keys = keep if keep is not None else list(ref)
    med = float(np.median([ref[k] for k in ref]))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in keys}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def worst_leaf_err(prog, ref) -> float:
    """max over leaves |prog - ref| (norm of the difference) / max(|ref|,
    median |ref| leaf)."""
    import jax
    diff = _leaf_norms(jax.tree_util.tree_map(
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
        prog, ref))
    ref_n = _leaf_norms(ref)
    med = float(np.median(list(ref_n.values())))
    return max(diff[k] / max(ref_n[k], med, 1e-30) for k in ref_n)


def step_numbers(rec, res: ref_lib.RefResult) -> Dict[str, float]:
    """loss_gap, grad_gap, grad_err and change_gap of the recorded
    steps."""
    import jax
    n = len(res.losses)
    loss_gap = max(abs(rec.losses[t] - res.losses[t])
                   / max(abs(res.losses[t]), 1e-30) for t in range(n))
    grad1 = jax.tree_util.tree_map(lambda m: np.asarray(m, np.float64)
                                   / (1 - B1), rec.mu1)
    g_ref = _leaf_norms(res.grads[0])
    grad_gap, _ = worst_leaf_gap(_leaf_norms(grad1), g_ref)
    grad_err = worst_leaf_err(grad1, res.grads[0])
    med_g = float(np.median(list(g_ref.values())))
    keep = [k for k, v in g_ref.items() if v >= 1e-3 * med_g]
    d_prog = _leaf_norms(jax.tree_util.tree_map(
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
        rec.params_n, rec.params0))
    d_ref = _leaf_norms(jax.tree_util.tree_map(
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
        res.params, res.params0))
    change_gap, _ = worst_leaf_gap(d_prog, d_ref, keep)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap, "grad_err": grad_err,
            "change_gap": change_gap}


def push_numbers(prog_cache: List[list], res: ref_lib.RefResult,
                 step: int) -> Dict[str, float]:
    """push_mismatch and push_gap of the HECs after recorded ``step``."""
    both = either = 0
    worst = 0.0
    for l, per_rank in enumerate(prog_cache):
        for r, (vids, rows) in enumerate(per_rank):
            live = res.cache[r].live(l, step)
            mine = dict(zip(vids.tolist(), range(len(vids))))
            common = [v for v in live if v in mine]
            both += len(common)
            either += len(set(live) | set(mine))
            if not common:
                continue
            want = np.stack([live[v] for v in common]).astype(np.float64)
            got = rows[[mine[v] for v in common]].astype(np.float64)
            norms = np.linalg.norm(want, axis=1)
            scale = np.maximum(norms, np.median(norms))
            err = np.linalg.norm(got - want, axis=1) / np.maximum(scale, 1e-30)
            worst = max(worst, float(err.max()))
    if either == 0:
        return {}
    return {"push_mismatch": 1.0 - both / either, "push_gap": worst}


def as_recording(res: ref_lib.RefResult, cached: bool):
    """A reference run dressed as a program recording, so that it can take
    the program's place in the comparison."""
    import types

    import jax
    caches = []
    if cached:
        step = len(res.losses)
        caches = [[[_cache_lines(c.live(l, step)) for c in res.cache]
                   for l in range(len(res.cache[0].rows))]]
    return types.SimpleNamespace(
        losses=res.losses, params0=res.params0, params_n=res.params,
        mu1=jax.tree_util.tree_map(lambda g: (1 - B1) * g, res.grads[0]),
        caches=caches)


def _cache_lines(live: dict):
    vids = np.fromiter(live.keys(), np.int64, len(live))
    rows = np.stack(list(live.values())) if live else np.zeros((0, 1))
    return vids, rows


class Check:
    """The float32 reference over the recorded run's batches, and the
    numbers of the program -- or of a stand-in put in its place: the
    control (the reference in bfloat16, one precision lower), or the
    reference with half of each batch left out, or with the exchange
    between chips left out."""

    def __init__(self, rec, ps, graph, m: ref_lib.Model, hec: dict,
                 seed: int):
        self.rec, self.m, self.hec, self.seed = rec, m, hec, seed
        self.graph = graph
        self.steps = rank_batches(rec.mbs, rec.seeds, ps, graph, m.num_layers)
        self.faults = float(batch_faults(self.steps, rec.mbs, graph,
                                         m.fanouts))
        self.owes = owes_table(ps)
        self.res = self.reference()

    def reference(self, precision="float32", steps=None,
                  owes=None) -> ref_lib.RefResult:
        return ref_lib.run_reference(
            self.m, self.graph.features, steps or self.steps,
            owes if owes is not None else self.owes, self.seed,
            nc=self.hec["push_limit"], delay=self.hec["delay"],
            life_span=self.hec["life_span"], precision=precision)

    def numbers(self, recording) -> Dict[str, float]:
        out = step_numbers(recording, self.res)
        out["batch_faults"] = self.faults
        if recording.caches:
            out.update(push_numbers(recording.caches[-1], self.res,
                                    len(self.res.losses)))
        return out

    def program(self) -> Dict[str, float]:
        return self.numbers(self.rec)

    def control(self) -> Dict[str, float]:
        return self.numbers(as_recording(self.reference("bfloat16"),
                                         bool(self.rec.caches)))

    def half_batch(self) -> Dict[str, float]:
        import dataclasses
        half = [[dataclasses.replace(
            b, seed_mask=b.seed_mask & (np.arange(len(b.seed_mask))
                                        < len(b.seed_mask) // 2))
            for b in row] for row in self.steps]
        return self.numbers(as_recording(self.reference(steps=half),
                                         bool(self.rec.caches)))

    def no_exchange(self) -> Dict[str, float]:
        none = [np.zeros_like(o) for o in self.owes]
        return self.numbers(as_recording(self.reference(owes=none),
                                         bool(self.rec.caches)))


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, [(name, value, limit)]): every number at or under its
    limit; a number without a limit, or a limit without its number, fails."""
    rows = []
    ok = True
    for name in sorted(set(numbers) | set(limits)):
        v = numbers.get(name)
        lim = limits.get(name)
        rows.append((name, v, lim))
        if v is None or lim is None or not np.isfinite(v) or v > lim:
            ok = False
    return ok, rows
