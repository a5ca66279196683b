"""Alg-2 rounds (reconstruct, estimate, reassign) per answer of the window:
the program's ``retrieval_iterations_total`` over the answers."""


def read(r):
    n = len(r.answers)
    total = r.counters.get("retrieval_iterations_total")
    return total / n if total is not None and n else None
