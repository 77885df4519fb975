"""Production meshes.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init.
"""
from __future__ import annotations

import jax

# TPU v5e hardware constants used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12       # per chip, FLOP/s
HBM_BW = 819e9                 # per chip, bytes/s
ICI_BW = 50e9                  # per link, bytes/s


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes))


def make_gnn_mesh(num_ranks: int):
    """1-D mesh for the paper's rank-per-partition GNN trainer.

    The axis is ``Auto``: the trainer and the serve schedulers place
    ``[R, ...]`` arrays with ``NamedSharding`` and run host-side
    ``jit(vmap(...))`` lookups over them, which jax's default ``Explicit``
    axes refuse ("inconsistent axis specs")."""
    return jax.make_mesh((num_ranks,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
