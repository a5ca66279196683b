"""Bit-planes the session readers newly took per answer of the window,
summed over variables and coefficient groups: the program's
``retrieval_planes_total`` over the answers."""


def read(r):
    n = len(r.answers)
    total = r.counters.get("retrieval_planes_total")
    return total / n if total is not None and n else None
