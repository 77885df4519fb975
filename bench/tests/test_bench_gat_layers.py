"""The program's GAT against the plain reference at the paper's Table-2
layer pattern, at small widths on the CPU: three layers, four heads on
the two hidden layers and one head on the class layer, neighbour slots
left empty (-1), a destination row with no neighbour at all, and source
rows that are not valid.  Both sides get the same seeded random weights
and the same position-hash dropout.  Compared: the hidden layers' outputs
(after dropout, read through the reference's push rows), the masked mean
loss and the step-1 gradient of every leaf.

Tolerance 1e-5, relative: under ``jax.default_matmul_precision("highest")``
both sides compute in float32 and differ only in the order of their sums
(the reference projects each source row where it gathers it, in blocks),
which reads under 5e-7 here.  The same comparison with the reference in
bfloat16, one precision lower, reads 0.2 on the gradient."""
from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

from core import reference as ref_lib  # noqa: E402
from core.check import worst_leaf_err  # noqa: E402
from repro.models.gnn import gat  # noqa: E402
from repro.train.loss import softmax_xent  # noqa: E402

TOL = 1e-5
M = ref_lib.Model(model="gat", feat_dim=12, hidden=8, num_classes=5,
                  num_layers=3, num_heads=4, fanouts=(3, 4, 5), batch_size=6,
                  dropout=0.5, lr=1e-3)
ROWS = (120, 50, 20, 6)          # rows feeding layer 0, 1, 2 and the seeds
SEED = 2147483659                # the step's dropout seed


def _batch():
    rng = np.random.default_rng(7)
    nbr = []
    for k, f in enumerate(M.fanouts):
        idx = rng.integers(0, ROWS[k], (ROWS[k + 1], f)).astype(np.int32)
        idx[rng.random(idx.shape) < 0.3] = -1          # empty slots
        idx[1] = -1                                    # a row with none
        nbr.append(idx)
    h0 = rng.standard_normal((ROWS[0], M.feat_dim)).astype(np.float32)
    valid0 = rng.random(ROWS[0]) > 0.2                 # invalid sources
    labels = rng.integers(0, M.num_classes, ROWS[-1]).astype(np.int32)
    seed_mask = np.ones(ROWS[-1], bool)
    seed_mask[-1] = False
    return h0, valid0, nbr, labels, seed_mask


def _program(params, h0, valid0, nbr, labels, seed_mask):
    """The program's GAT forward and the trainer's masked mean loss; the
    hidden layers' outputs as its halo hook sees them."""
    def loss_fn(params):
        seen = {}

        def hook(k, h, valid):
            seen[k] = h
            return h, valid

        out, valid = gat.forward(params, h0, valid0, {"nbr_idx": nbr},
                                 dropout=M.dropout, seed=jnp.uint32(SEED),
                                 halo_hook=hook)
        B = labels.shape[0]
        loss = softmax_xent(out[:B].astype(jnp.float32), labels,
                            seed_mask & valid[:B])
        return loss, [seen[k] for k in range(1, M.num_layers)]

    (loss, hidden), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    return float(loss), grads, hidden


def _reference(params, h0, valid0, nbr, labels, seed_mask, precision):
    """The reference's step on the same batch: mean loss, the gradient of
    the mean, and (through push rows covering every row) the inputs of
    layers 1 and 2."""
    subs = [(jnp.zeros(n, bool), jnp.zeros(n, bool),
             jnp.zeros((n, M.hidden * M.num_heads)))
            for n in ROWS[1:M.num_layers]]
    push_pos = jnp.arange(ROWS[1])
    nll, (n_valid, _, pushed), grads = ref_lib.rank_step(
        params, h0, valid0, nbr, subs, labels, seed_mask, jnp.uint32(SEED),
        push_pos, m=M, precision=precision)
    n = float(n_valid)
    grads = jax.tree_util.tree_map(lambda g: g / n, grads)
    hidden = [emb[:ROWS[k]] for k, (emb, _) in enumerate(pushed) if k >= 1]
    return float(nll) / n, grads, hidden


@pytest.fixture(scope="module")
def readings():
    params = ref_lib.init_params(jax.random.key(11), M)
    batch = tuple(jnp.asarray(x) if not isinstance(x, list)
                  else [jnp.asarray(a) for a in x] for x in _batch())
    with jax.default_matmul_precision("highest"):
        prog = _program(params, *batch)
        ref = {p: _reference(params, *batch, precision=p)
               for p in ("float32", "bfloat16")}
    return prog, ref


def test_layer_pattern_is_table_2s():
    """Four heads on the hidden layers, one on the class layer; every
    layer has empty slots and a row with no neighbour."""
    params = ref_lib.init_params(jax.random.key(11), M)
    heads = [p["w"].shape[1] for p in params["layers"]]
    assert heads == [4, 4, 1]
    _, valid0, nbr, _, _ = _batch()
    assert all((x == -1).any() and (x[1] == -1).all() for x in nbr)
    assert not valid0.all()


@pytest.mark.parametrize("part", ["hidden", "loss", "grads"])
def test_gat_follows_the_reference(readings, part):
    (loss, grads, hidden), ref = readings
    r_loss, r_grads, r_hidden = ref["float32"]
    if part == "hidden":
        for h, r in zip(hidden, r_hidden):
            scale = float(jnp.abs(r).max())
            assert scale > 0
            assert float(jnp.abs(h - r).max()) / scale < TOL
    elif part == "loss":
        assert abs(loss - r_loss) / abs(r_loss) < TOL
    else:
        assert worst_leaf_err(grads, r_grads) < TOL


def test_one_precision_lower_fails_the_tolerance(readings):
    """The reference in bfloat16 is told apart by the gradient."""
    (_, grads, _), ref = readings
    assert worst_leaf_err(grads, ref["bfloat16"][1]) > 100 * TOL
