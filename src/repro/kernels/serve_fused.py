"""Fused per-layer serve kernel (the serving analogue of update_fused).

Online serving executes the same layer math as training — masked
neighbor gather, mean AGG, dense UPDATE — but with dropout off and over
the PR 5 block-diagonal fused rounds, whose padded rows are plain ``-1``
neighbor slots.  This kernel runs the gather, the masked mean, both
matmuls, bias, and ReLU as ONE ``pallas_call``: one dispatch per layer
instead of the composed chain, and no separate self-activation operand —
the dst rows are read straight from the ``h_src`` prefix inside the
kernel (the serve blocks' dst-prefix invariant).

Memory spaces: every operand is passed as a whole-array ``ANY``-space
ref rather than through gridded ``BlockSpec`` windows, and read with
``[...]``.  Only the Pallas interpreter accepts that: Mosaic loads from
VMEM and SMEM alone, so on a TPU the kernel needs a rewrite that
re-blocks the dst rows over a grid like ``update_fused`` and DMAs
``h_src`` tiles on demand.  Until then ``require_interpreter`` refuses
it on every backend but the CPU.

Parity: the in-kernel math is ``kernels.ref.serve_layer_ref`` op-for-op
— bit-exact, pinned in tests/test_kernels.py — and both online
schedulers keep the composed path as the default: ``fused_kernel=False``
is byte-identical because this module is never imported.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


def require_interpreter():
    """Raise unless the backend runs Pallas kernels in the interpreter."""
    if not interpret_mode():
        raise NotImplementedError(
            f"fused_kernel=True is not supported on the "
            f"{jax.default_backend()!r} backend: the fused serve layer "
            f"loads whole ANY-space refs, which the Mosaic compiler "
            f"refuses; serve with fused_kernel=False")


def _serve_kernel(nbr_ref, h_ref, valid_ref, wn_ref, ws_ref, b_ref,
                  out_ref, *, relu: bool):
    nbr = nbr_ref[...]                            # [M, f] int32
    h = h_ref[...]                                # [N, D]
    valid = valid_ref[...]                        # [N] bool
    idx = jnp.maximum(nbr, 0)
    mask = (nbr >= 0) & valid[idx]
    feats = h[idx]                                # [M, f, D]
    m = mask[..., None].astype(h.dtype)
    s = (feats * m).sum(axis=1)
    cnt = m.sum(axis=1)
    agg = s / jnp.maximum(cnt, 1.0)
    self_h = h[: nbr.shape[0]]                    # dst-prefix invariant
    acc = jnp.dot(agg, wn_ref[...], preferred_element_type=jnp.float32)
    acc += jnp.dot(self_h, ws_ref[...],
                   preferred_element_type=jnp.float32)
    acc += b_ref[...][None, :].astype(jnp.float32)
    if relu:
        acc = jnp.maximum(acc, 0.0)
    out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("relu",))
def fused_serve_layer(h_src, nbr_idx, src_valid, wn, ws, b, *, relu=True):
    """One serve layer in one pallas_call.

    h_src [N, D] source activations; nbr_idx [M, f] (-1 pad);
    src_valid [N] bool; wn/ws [D, K]; b [K] -> [M, K] float32.

    Self rows are the ``h_src[:M]`` prefix (the serve blocks' dst-prefix
    invariant — same contract as ``graphsage.forward``), read in-kernel
    rather than passed as an operand.
    """
    require_interpreter()
    M, _ = nbr_idx.shape
    K = wn.shape[1]
    spec = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_serve_kernel, relu=relu),
        grid=(1,),
        in_specs=[spec] * 6,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((M, K), jnp.float32),
        interpret=True,
    )(nbr_idx.astype(jnp.int32), h_src, src_valid.astype(jnp.bool_),
      wn, ws, b)


def forward(params, h0, valid0, blocks, *, dropout: float = 0.0,
            seed=None, halo_hook=None, use_kernel: bool = True):
    """Drop-in for ``graphsage.forward`` on the serve path (dropout off).

    Same signature and hook contract: halo_hook(k, h, valid) runs on the
    host-jnp side between fused layer calls, exactly where the composed
    path runs it.  Serving never uses dropout, so the hash-dropout tail
    is not part of this kernel; asserting keeps the contract loud.
    """
    del seed, use_kernel
    assert float(dropout) == 0.0, "fused serve kernel is dropout-free"
    h, valid = h0, valid0
    if halo_hook is not None:
        h, valid = halo_hook(0, h, valid)
    L = len(params["layers"])
    for k in range(L):
        nbr = blocks["nbr_idx"][k]
        n_dst = nbr.shape[0]
        p = params["layers"][k]
        last = k == L - 1
        h_new = fused_serve_layer(h, nbr, valid, p["wn"], p["ws"], p["b"],
                                  relu=not last)
        valid = valid[:n_dst]
        if halo_hook is not None and not last:
            h_new, valid = halo_hook(k + 1, h_new, valid)
        h = h_new
    return h, valid
