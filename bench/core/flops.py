"""Operations of one training step, from the configuration alone.

Counts the multiply-adds of the forward pass at the block capacities
(every sampled row present: batch ``B`` seeds, ``B * prod(1 + f)`` rows
further out) and triples them for forward plus backward.  Deduplicated
frontiers are smaller than the capacities, so this is an upper bound on
the work a step requires; it moves only when the configuration does.

Counted: the dense projections of every layer (GraphSAGE: ``W_n`` and
``W_s`` on each destination row; GAT: ``W`` on every source row), and for
GAT the attention (the two score dot products on every source row and the
weighted sum over each destination row's neighbours).  Not counted:
elementwise work, the mean aggregation's adds, softmax, the loss and the
optimizer.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

FWD_BWD = 3          # backward costs two forwards (input and weight grads)


def capacities(batch_size: int, fanouts: Sequence[int]) -> List[int]:
    """Rows per block, input side first: ``caps[k]`` rows feed layer k,
    ``caps[k + 1]`` of them are its destinations."""
    caps = [batch_size]
    for f in reversed(list(fanouts)):
        caps.append(caps[-1] * (1 + f))
    return caps[::-1]


def step_flops(model: str, batch_size: int, fanouts: Sequence[int],
               feat_dim: int, hidden: int, num_classes: int,
               num_heads: int = 1) -> Dict[str, float]:
    """FLOP of one forward + backward step by part (``projection``, and for
    GAT ``scores`` and ``aggregation``); ``total`` sums them."""
    caps = capacities(batch_size, fanouts)
    L = len(fanouts)
    parts = {"projection": 0.0, "scores": 0.0, "aggregation": 0.0}
    if model == "graphsage":
        dims = [feat_dim] + [hidden] * (L - 1) + [num_classes]
        for k in range(L):
            parts["projection"] += 2 * caps[k + 1] * dims[k] * dims[k + 1]
    elif model == "gat":
        d_in = [feat_dim] + [hidden * num_heads] * (L - 1)
        d_out = [hidden * num_heads] * (L - 1) + [num_classes]
        for k in range(L):
            parts["projection"] += caps[k] * d_in[k] * d_out[k]
            parts["scores"] += 2 * caps[k] * d_out[k]          # e_u, e_v
            parts["aggregation"] += caps[k + 1] * fanouts[k] * d_out[k]
    else:
        raise ValueError(f"no FLOP count for model {model!r}")
    out = {k: 2.0 * FWD_BWD * v for k, v in parts.items()}
    out["total"] = sum(out.values())
    return out
