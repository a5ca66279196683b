"""Bytes the sessions moved (each reply's ``bytes_moved``) per certified
answer: the paper's transfer cost."""


def read(r):
    n = len(r.certified)
    return sum(a.bytes_moved for a in r.answers) / n if n else None
