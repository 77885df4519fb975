"""host_prep_ms (ms per step, program spans): the host's busy time per
window step in the pipeline's phase spans -- ``phase_seconds`` of
``sample`` (CSR fanout draw), ``host_prep`` (stacking the ranks'
minibatches) and ``stage`` (issuing the host-to-device copy) -- from the
program's obs registry.  Sampling runs on a prefetch thread, so this is
busy time, not all of it on the step's critical path."""

PHASES = ("sample", "host_prep", "stage")


def read(record):
    run = record["run"]
    if not run.steps:
        return None
    busy = sum(run.phase_s.get(p, 0.0) for p in PHASES)
    return 1e3 * busy / run.steps if busy > 0 else None
