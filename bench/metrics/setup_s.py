"""setup_s (s, host clock): from process start to the start of the
window -- loading or building the graph and partition, ``setup_gnn``,
compilation (from the persistent cache after a cell's first run) and
the warm-up epoch."""


def read(record):
    return record["run"].setup_s
