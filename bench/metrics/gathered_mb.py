"""gathered_mb: bytes one gathered refresh moves between the sites
(``comm.bytes`` over ``comm.rounds`` in the window), in MB."""


def read(run):
    rounds = run.counter("comm.rounds")
    if rounds <= 0:
        return None
    return run.counter("comm.bytes") / rounds / 1e6
