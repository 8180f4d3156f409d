"""root_resident_pct: the share of the live root rows the window's
refreshes found already on the device (``refresh.root_rows_resident``
over it plus ``refresh.root_rows_uploaded``), in percent.  Nothing where
the program does not count them."""


def read(run):
    resident = run.counter("refresh.root_rows_resident")
    total = resident + run.counter("refresh.root_rows_uploaded")
    if total <= 0:
        return None
    return 100.0 * resident / total
