"""The plain reference against the program's timed path at test size on
the CPU: one rank (GraphSAGE, GAT) and four virtual devices (GraphSAGE,
with the AEP push).  On the CPU float32 matrix products are exact to
rounding, so every gap reads under 1e-5; the minibatches break no
invariant; on four ranks the HEC lines the program holds are the
reference's own pushes.  And the reference's own numbers at a fixed tiny
seed (two ranks, its push and cache live), pinned bit for bit per
model."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def cpu_cell(ranks, model, faults, cache):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(HERE, "cpu_cell.py"),
                        str(ranks), model, ",".join(faults), str(cache)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("ranks,model", [(1, "graphsage"), (1, "gat"),
                                         (4, "graphsage")])
def test_reference_follows_the_timed_path(ranks, model, tmp_path):
    got = cpu_cell(ranks, model, ["none"], tmp_path)["none"]
    assert got["batch_faults"] == 0
    for name in ("loss_gap", "grad_gap", "grad_err", "change_gap"):
        assert got[name] < 1e-5, (name, got)
    if ranks > 1:
        assert got["push_mismatch"] == 0 and got["push_gap"] < 1e-5
    else:
        assert "push_mismatch" not in got


# ---------------------------------------------------------------------------
# the reference's own numbers, pinned
# ---------------------------------------------------------------------------
def tiny_reference(ref_lib, model: str):
    """Three steps of the reference alone on two ranks of a 60-vertex
    graph (three layers, fanouts 2/3/4, dropout 0.5, the push and the
    reference's cache live): the three losses and step 1's gradient,
    as Adam got it, by leaf norm."""
    import jax
    m = ref_lib.Model(model=model, feat_dim=12, hidden=8, num_classes=5,
                      num_layers=3, num_heads=2, fanouts=(2, 3, 4),
                      batch_size=4, dropout=0.5, lr=0.01)
    R, V = 2, 60
    rng = np.random.default_rng(5)
    owner = np.arange(V) % R
    features = rng.standard_normal((V, m.feat_dim)).astype(np.float32)
    extra = (15, 10, 6)                 # rows layer k adds to layer k + 1's
    steps = []
    for t in range(3):
        row = []
        for r in range(R):
            own = rng.permutation(np.flatnonzero(owner == r))
            order = np.concatenate([own[:m.batch_size], rng.permutation(
                np.setdiff1d(np.arange(V), own[:m.batch_size]))])
            n = [m.batch_size + sum(extra[k:]) for k in range(3)]
            n.append(m.batch_size)
            vids = [np.append(order[:n[0]], -1)] + [order[:k] for k in n[1:]]
            nbr = []
            for k, f in enumerate(m.fanouts):
                idx = rng.integers(0, len(vids[k]), (n[k + 1], f))
                idx[rng.random(idx.shape) < 0.25] = -1
                nbr.append(idx.astype(np.int32))
            seed_mask = np.arange(m.batch_size) < m.batch_size - 1
            row.append(ref_lib.RankBatch(
                vids=vids,
                solid=[(v >= 0) & (owner[np.maximum(v, 0)] == r) for v in vids],
                nbr_idx=nbr,
                labels=rng.integers(0, m.num_classes, m.batch_size).astype(
                    np.int32),
                seed_mask=seed_mask, seed=1000 + t))
        steps.append(row)
    owes = [(owner == r)[None, :] & (rng.random((R, V)) < 0.5)
            & (np.arange(R) != r)[:, None] for r in range(R)]
    res = ref_lib.run_reference(m, features, steps, owes, 2147483677, nc=3,
                                delay=1, life_span=2)
    norms = {jax.tree_util.keystr(p): float(np.linalg.norm(
        np.asarray(g, np.float64)))
        for p, g in jax.tree_util.tree_leaves_with_path(res.grads[0])}
    return res.losses, norms


# Recorded on the CPU with tiny_reference from the reference as it stood
# before each model's maths moved into bench/models/.
PINNED = {
    "graphsage": (
        [4.835265477498372, 12.82115109761556, 4.696844736735026],
        {
            "['layers'][0]['b']": 0.10263972900007597,
            "['layers'][0]['wn']": 0.23143895350543853,
            "['layers'][0]['ws']": 0.3923070792158726,
            "['layers'][1]['b']": 0.06291787938342218,
            "['layers'][1]['wn']": 0.2873664364496704,
            "['layers'][1]['ws']": 0.473894776346474,
            "['layers'][2]['b']": 0.05530128060887206,
            "['layers'][2]['wn']": 0.15987516441769414,
            "['layers'][2]['ws']": 0.6650290983780964,
        }),
    "gat": (
        [2.6740509668986, 1.0715957482655842, 1.6211109161376953],
        {
            "['layers'][0]['a_u']": 0.0734452674372789,
            "['layers'][0]['a_v']": 0.013255500040955636,
            "['layers'][0]['b']": 0.20436880586879552,
            "['layers'][0]['w']": 0.37661542957859234,
            "['layers'][1]['a_u']": 0.13030701964064142,
            "['layers'][1]['a_v']": 1.0915034787749253e-08,
            "['layers'][1]['b']": 0.15981713490839936,
            "['layers'][1]['w']": 0.46416500375734376,
            "['layers'][2]['a_u']": 0.3816979906223208,
            "['layers'][2]['a_v']": 8.474411349764796e-08,
            "['layers'][2]['b']": 0.11170774214307812,
            "['layers'][2]['w']": 0.628236947560427,
        }),
}


@pytest.mark.parametrize("model", ["graphsage", "gat"])
def test_reference_numbers_are_pinned(model):
    """The reference's three losses and step 1's gradient leaf norms at a
    fixed tiny seed, bit for bit."""
    sys.path.insert(0, BENCH)
    from core import reference as ref_lib
    losses, norms = tiny_reference(ref_lib, model)
    want_losses, want_norms = PINNED[model]
    assert losses == want_losses
    assert norms == want_norms
