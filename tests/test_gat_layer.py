"""``gat.gat_layer`` against the plain GAT formula, and the order it takes.

The plain formula, written here, projects every source row and then
gathers the projection per edge.  ``gat_layer`` gathers first wherever its
input is narrower than the projection (``din < H*dh``); the two orders
must agree in outputs and in the gradients of ``w``, ``b``, ``a_u`` and
``a_v``.  Tolerance 1e-5, relative to the largest entry: under
``jax.default_matmul_precision("highest")`` both sides compute in float32
and differ only in the order of their sums.

The structural test lowers the gradient of a three-layer GAT at the paper's
Table-2 layer pattern (input narrower than the projection, then as wide,
then wider) and reads which scatters its backward holds.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.gnn import gat

TOL = 1e-5
N_SRC, N_DST, FANOUT = 40, 12, 5


def _plain(p, h_src, nbr_idx, valid):
    """Project every source row, gather per edge, softmax over the valid
    slots; a destination with no valid slot reads zeros."""
    z = jax.nn.relu(jnp.einsum("nd,dhe->nhe", h_src, p["w"]) + p["b"])
    n_dst = nbr_idx.shape[0]
    idx = jnp.maximum(nbr_idx, 0)
    mask = ((nbr_idx >= 0) & valid[idx])[..., None]        # [N_dst, f, 1]
    e_u = (z * p["a_u"]).sum(-1)[idx]                      # [N_dst, f, H]
    e_v = (z[:n_dst] * p["a_v"]).sum(-1)[:, None, :]       # [N_dst, 1, H]
    s = jnp.where(mask, jax.nn.leaky_relu(e_u + e_v, 0.2), 0.0)
    top = jnp.max(jnp.where(mask, s, -jnp.inf), axis=1, keepdims=True)
    top = jnp.where(jnp.isfinite(top), top, 0.0)
    ex = jnp.where(mask, jnp.exp(s - top), 0.0)
    alpha = ex / jnp.maximum(ex.sum(1, keepdims=True), 1e-30)
    h = (alpha[..., None] * z[idx]).sum(1)                 # [N_dst, H, dh]
    return h.reshape(n_dst, -1)


def _inputs(din, H, dh):
    rng = np.random.default_rng(din * 100 + H * 10 + dh)
    p = gat.init_params(jax.random.key(din), din, dh, 3, 2, H)["layers"][0]
    p = dict(p, b=jnp.asarray(rng.normal(0, 0.1, (H, dh)), jnp.float32))
    h_src = jnp.asarray(rng.standard_normal((N_SRC, din)), jnp.float32)
    nbr = rng.integers(0, N_SRC, (N_DST, FANOUT)).astype(np.int32)
    nbr[rng.random(nbr.shape) < 0.3] = -1                  # empty slots
    nbr[2] = -1                                            # a row with none
    valid = rng.random(N_SRC) > 0.25                       # invalid sources
    valid[nbr[5, 0]] = False                               # one of them used
    assert nbr[5, 0] >= 0
    cot = jnp.asarray(rng.standard_normal((N_DST, H * dh)), jnp.float32)
    return p, h_src, jnp.asarray(nbr), jnp.asarray(valid), cot


def _rel(a, b):
    scale = float(jnp.abs(b).max())
    assert scale > 0
    return float(jnp.abs(a - b).max()) / scale


def _scatter_rows(hlo: str):
    """Leading dimension of every scatter's result (its first operand's
    shape) in an HLO module's text."""
    return [int(m.group(1)) for m in
            re.finditer(r"=\s*\w+\[(\d+)[,\]][^=\n]*\sscatter\(", hlo)]


@pytest.mark.parametrize("din,H,dh,gathers_first", [
    (12, 4, 8, True),       # 12 < 32: gather, then project
    (32, 4, 8, False),      # 32 = 32: project, then gather
])
def test_gat_layer_matches_the_plain_formula(din, H, dh, gathers_first):
    p, h_src, nbr, valid, cot = _inputs(din, H, dh)

    def loss(fn):
        return lambda p: (fn(p, h_src, nbr, valid) * cot).sum()

    def layer(p, h, n, v):
        return gat.gat_layer(p, h, n, v, layer=0)

    with jax.default_matmul_precision("highest"):
        out, ref = layer(p, h_src, nbr, valid), _plain(p, h_src, nbr, valid)
        grads, r_grads = jax.grad(loss(layer))(p), jax.grad(loss(_plain))(p)
        hlo = jax.jit(jax.grad(loss(layer))).lower(p).compiler_ir(
            "hlo").as_hlo_text()
    assert _rel(out, ref) < TOL
    assert float(jnp.abs(out[2]).max()) == 0.0            # no neighbour
    for leaf in ("w", "b", "a_u", "a_v"):
        assert _rel(grads[leaf], r_grads[leaf]) < TOL, leaf
    # the order taken: gathering first leaves no scatter into source rows
    assert (N_SRC in _scatter_rows(hlo)) != gathers_first


def test_layer0_backward_scatters_nothing_into_its_source_rows():
    """Table-2 pattern at small widths: features 12 -> 4 heads x 8 (layer 0
    gathers first), 32 -> 4 x 8 (layer 1 projects first), 32 -> 1 x 5.
    The gradient holds no scatter into layer 0's 120 source rows, and
    still holds layer 1's into its 50 (``z`` of [50, 4, 8])."""
    rows = (120, 50, 20, 6)
    rng = np.random.default_rng(3)
    params = gat.init_params(jax.random.key(0), 12, 8, 5, 3, 4)
    assert [tuple(p["w"].shape) for p in params["layers"]] == [
        (12, 4, 8), (32, 4, 8), (32, 1, 5)]
    nbr = []
    for k, f in enumerate((3, 4, 5)):
        idx = rng.integers(0, rows[k], (rows[k + 1], f)).astype(np.int32)
        idx[rng.random(idx.shape) < 0.3] = -1
        nbr.append(jnp.asarray(idx))
    h0 = jnp.asarray(rng.standard_normal((rows[0], 12)), jnp.float32)
    valid0 = jnp.asarray(rng.random(rows[0]) > 0.2)

    def loss(params):
        out, valid = gat.forward(params, h0, valid0, {"nbr_idx": nbr},
                                 dropout=0.5, seed=jnp.uint32(9))
        return (out.sum(-1) * valid).sum()

    hlo = jax.jit(jax.grad(loss)).lower(params).compiler_ir(
        "hlo").as_hlo_text()
    scattered = _scatter_rows(hlo)
    assert rows[0] not in scattered
    assert re.search(r"=\s*f32\[50,4,8\][^=\n]*\sscatter\(", hlo)
