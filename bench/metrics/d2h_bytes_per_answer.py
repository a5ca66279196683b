"""Bytes copied from the device to the host per certified answer: the
program's ``xfer_d2h_bytes_total`` (every contribution, decoded-value and
estimator result copy of the retrieval path) over the window."""


def read(r):
    n = len(r.certified)
    total = r.counters.get("xfer_d2h_bytes_total")
    return total / n if total is not None and n else None
