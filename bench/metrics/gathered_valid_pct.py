"""gathered_valid_pct: the share of the rows the sites put on the
exchange that are live records (``comm.records`` over ``comm.rows`` in
the window), in percent: the share of the gathered union, and of every
chip's replicated solve, that is not padding.  Nothing where the program
does not count the rows."""


def read(run):
    rows = run.counter("comm.rows")
    if rows <= 0:
        return None
    return 100.0 * run.counter("comm.records") / rows
