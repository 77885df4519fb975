"""epoch_s (s, host clock): the window's elapsed seconds over the whole
epochs completed in it.  It covers the host's per-epoch work and every
stall, so it is what a user of the trainer waits for."""


def read(record):
    run = record["run"]
    return run.window_s / run.epochs if run.epochs else None
