"""batch_occupancy: mean rows per scheduler tick over the tick's batch
cap (``serve.batch_occupancy``) in the window, in percent."""


def read(run):
    count, total = run.hist("serve.batch_occupancy")
    return 100.0 * total / count if count else None
