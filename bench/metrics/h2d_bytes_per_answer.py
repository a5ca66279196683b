"""Bytes handed from the host to the device per certified answer: the
program's ``xfer_h2d_bytes_total`` (plane words, shifts and sign bytes into
the decode, the host route's scattered fields, the estimator's value and
bound arrays) over the window."""


def read(r):
    n = len(r.certified)
    total = r.counters.get("xfer_h2d_bytes_total")
    return total / n if total is not None and n else None
