"""Jit'd public wrappers around the Pallas kernels (the backend picks
interpret or compiled mode, see ``repro.kernels.interpret_mode``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.gat_edge import gat_edge
from repro.kernels.hec_search import (hec_probe, hec_search_batched,
                                      hec_search_kernel)
from repro.kernels.sage_agg import sage_agg
from repro.kernels.serve_fused import fused_serve_layer
from repro.kernels.update_fused import fused_update

__all__ = ["fused_update", "sage_agg", "gat_edge", "gat_edge_aggregate",
           "hec_search_kernel", "hec_search_batched", "hec_probe",
           "fused_serve_layer"]


def gat_edge_aggregate(z, e_u, e_v, nbr_idx, src_valid):
    """Model-facing wrapper: gathers neighbor tensors, runs the kernel.

    z [N_src, H, dh]; e_u [N_src, H]; e_v [N_src, H] (dst rows are the
    prefix); nbr_idx [N_dst, f]; src_valid [N_src]. Returns [N_dst, H, dh].
    """
    n_dst, f = nbr_idx.shape
    H, dh = z.shape[1], z.shape[2]
    idx = jnp.maximum(nbr_idx, 0)
    mask = (nbr_idx >= 0) & src_valid[idx]
    eu_nbr = e_u[idx]                          # [M, f, H]
    z_nbr = z[idx].reshape(n_dst, f, H * dh)
    out = gat_edge(eu_nbr, e_v[:n_dst], z_nbr, mask, heads=H)
    return out.reshape(n_dst, H, dh)
