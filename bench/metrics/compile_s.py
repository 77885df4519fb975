"""compile_s (s, program counter): JAX's backend-compile seconds during
set-up, summed from ``jax.monitoring`` (the compile clock of
``chip_smoke.py``).  About 0 when the persistent cache serves every
program; it moves ``setup_s``."""


def read(record):
    return record["run"].compile_s
