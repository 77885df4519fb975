"""Operations of one training step, from the configuration alone.

Counts the multiply-adds of the forward pass at the block capacities
(every sampled row present: batch ``B`` seeds, ``B * prod(1 + f)`` rows
further out) and triples them for forward plus backward.  Deduplicated
frontiers are smaller than the capacities, so this is an upper bound on
the work a step requires; it moves only when the configuration does.

What is counted is the model's own: ``forward_flops(m, caps)`` of
``bench/models/<model>.py`` gives the forward multiply-adds by part
(GraphSAGE: ``projection``; GAT: ``projection``, ``scores`` and
``aggregation``).  Elementwise work, softmax, the loss and the optimizer
are not counted.
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


def step_flops(m) -> Dict[str, float]:
    """FLOP of one forward + backward step of the ``reference.Model``
    ``m``, by the parts its model file counts; ``total`` sums them."""
    parts = m.maths.forward_flops(m, capacities(m.batch_size, m.fanouts))
    out = {k: 2.0 * FWD_BWD * v for k, v in parts.items()}
    out["total"] = sum(out.values())
    return out
