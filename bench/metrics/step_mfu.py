"""step_mfu (%, host clock): the whole step's share of the chips' bf16
peak -- the benchmark's own FLOP count of a forward and backward step at
the configuration's block capacities (``core/flops.py``, an upper bound
on the work, fixed by the configuration), times the window's steps, over
the window's seconds and the chips' summed peak (``peaks.json``)."""


def read(record):
    run = record["run"]
    if not run.steps or run.window_s <= 0:
        return None
    peak = record["chips"] * record["peaks"]["bf16_flops"]
    return 100.0 * record["flops_per_step"] * run.steps / run.window_s / peak
