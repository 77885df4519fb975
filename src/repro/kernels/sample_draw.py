"""On-device fanout draw (drops the host ``np.random`` sampling loop).

The host vectorized sampler (pipeline/vectorized_sampler.py) draws
without replacement by Floyd's algorithm, one exact integer per pick;
its take-all rows (deg <= fanout) keep every neighbor in CSR order.  The
device path draws from the same distribution as a *selection-key*
problem that runs entirely on-device:

  1. expand each frontier row's CSR neighbor range to a dense [n, W]
     candidate matrix (W = max degree), -1 past the row's degree,
  2. a Pallas kernel assigns every candidate a float32 key via the
     repo-wide u32 mix hash (``ref.sample_keys_ref`` is the jnp oracle —
     bit-identical), policy-dependent:
       uniform  hash(row, slot)       iid neighbor sampling
       labor    hash(vid)             LABOR-style shared vertex keys
       cv       hash(vid)/weight      control-variate boost for vertices
                                      with HEC-resident activations
  3. rows with deg <= fanout take ALL neighbors in CSR order (keys
     overridden by slot index — bit-matching the host sampler's
     take-all rows), everything else keeps its f smallest keys via
     ``lax.top_k``.

Determinism: the seed is derived per (base_seed, epoch, step, rank,
layer) by ``jax.random`` fold_in chaining (see DeviceSampler in
vectorized_sampler.py), so the draw is a pure function of those — the
prefetcher's worker count can never change it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

# np scalars (not jnp) so the kernel body doesn't capture traced consts
_MIX1 = np.uint32(0x85EBCA6B)
_MIX2 = np.uint32(0xC2B2AE35)

_POLICIES = ("uniform", "labor", "cv")


def _keys_kernel(nbr_ref, w_ref, seed_ref, out_ref, *, policy: str,
                 bn: int, width: int):
    i = pl.program_id(0)
    nbr = nbr_ref[...]                              # [bn, W] int32
    if policy == "uniform":
        a = (i * bn + jax.lax.broadcasted_iota(jnp.int32, (bn, width), 0)
             ).astype(jnp.uint32)
        b = jax.lax.broadcasted_iota(jnp.int32, (bn, width), 1
                                     ).astype(jnp.uint32)
    else:
        a = jnp.maximum(nbr, 0).astype(jnp.uint32)
        b = jnp.zeros_like(a)
    h = (a * _MIX1) ^ (b * _MIX2) ^ seed_ref[0]
    h = h ^ (h >> np.uint32(15))
    h = h * _MIX1
    h = h ^ (h >> np.uint32(13))
    # the top 24 bits fit int32 exactly; Mosaic has no u32 -> f32 cast
    keys = (h >> np.uint32(8)).astype(jnp.int32).astype(jnp.float32) \
        / np.float32(1 << 24)
    if policy == "cv":
        keys = keys / jnp.maximum(w_ref[...], 1e-6)
    out_ref[...] = jnp.where(nbr >= 0, keys, jnp.inf)


@functools.partial(jax.jit, static_argnames=("policy", "bn"))
def sample_keys_kernel(seed, nbr_vid, weights=None, *, policy="uniform",
                       bn=1024):
    """Selection keys [n, W] float32 (+inf on -1 slots); f smallest win.

    Bit-matches ``kernels.ref.sample_keys_ref`` (pinned in tests).
    """
    assert policy in _POLICIES, policy
    n, width = nbr_vid.shape
    pad_n = (-n) % bn if n > bn else 0
    bn = min(bn, max(n, 1))
    nbr_vid = nbr_vid.astype(jnp.int32)
    if weights is None:
        weights = jnp.ones((n, width), jnp.float32)
    if pad_n:
        nbr_vid = jnp.pad(nbr_vid, ((0, pad_n), (0, 0)), constant_values=-1)
        weights = jnp.pad(weights, ((0, pad_n), (0, 0)), constant_values=1.0)
    np_ = n + pad_n
    seed_arr = jnp.asarray([seed], jnp.uint32)
    out = pl.pallas_call(
        functools.partial(_keys_kernel, policy=policy, bn=bn, width=width),
        grid=(np_ // bn,),
        in_specs=[
            pl.BlockSpec((bn, width), lambda i: (i, 0)),
            pl.BlockSpec((bn, width), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bn, width), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, width), jnp.float32),
        interpret=interpret_mode(),
    )(nbr_vid, weights.astype(jnp.float32), seed_arr)
    return out[:n]


@functools.partial(jax.jit, static_argnames=(
    "f", "num_solid", "width", "policy", "use_kernel"))
def draw_neighbors_device(indptr, indices, wtab, cur, seed, allow, *,
                          f: int, num_solid: int, width: int,
                          policy: str = "uniform", use_kernel: bool = True):
    """Device analogue of the host ``_draw_neighbors``: [n] -> [n, f].

    indptr [S+1], indices [E] — the partition's solid CSR (int32 on
    device); wtab [S+H] float32 — per-vertex cv weights (ignored unless
    policy == "cv"); cur [n] frontier VID_p (-1/halo rows draw nothing);
    seed uint32; allow [n] bool or None.

    Matches the host contract exactly: invalid rows are all -1; rows
    with deg <= f take every neighbor in CSR order left-packed; bigger
    rows keep the f candidates with smallest selection keys.
    """
    n = cur.shape[0]
    cur = cur.astype(jnp.int32)
    valid = (cur >= 0) & (cur < num_solid)
    if allow is not None:
        valid = valid & allow
    vc = jnp.where(valid, cur, 0)
    deg = jnp.where(valid, indptr[vc + 1] - indptr[vc], 0)
    starts = indptr[vc]
    col = jnp.arange(width, dtype=jnp.int32)
    in_row = col[None, :] < deg[:, None]
    num_edges = indices.shape[0]
    if num_edges == 0:
        return jnp.full((n, f), -1, jnp.int32)
    gi = jnp.minimum(starts[:, None] + col[None, :], num_edges - 1)
    nbr = jnp.where(in_row, indices[gi].astype(jnp.int32), -1)   # [n, W]
    if width < f:                     # every row is take-all; widen for top_k
        nbr = jnp.pad(nbr, ((0, 0), (0, f - width)), constant_values=-1)
        col = jnp.arange(f, dtype=jnp.int32)
    w = wtab[jnp.maximum(nbr, 0)] if policy == "cv" else None
    if use_kernel:
        keys = sample_keys_kernel(seed, nbr, w, policy=policy)
    else:
        from repro.kernels import ref
        keys = ref.sample_keys_ref(seed, nbr, w, policy=policy)
    # take-all rows: CSR order beats the random keys (host bit-contract)
    small = (deg <= f)[:, None]
    csr_keys = jnp.where(nbr >= 0, col[None, :].astype(jnp.float32),
                         jnp.inf)
    keys = jnp.where(small, csr_keys, keys)
    _, sel = jax.lax.top_k(-keys, f)
    return jnp.take_along_axis(nbr, sel, axis=1)
