"""Device milliseconds of the scatter-recompose programs
(``transform/hierarchical.py`` ``scatter_recompose_from*``,
``recompose_hb_from``) per answer of the traced window."""

PROGRAMS = r"scatter_recompose|recompose_hb_from"


def read(r):
    n = len(r.answers)
    if r.trace is None or not n:
        return None
    s = r.trace.program_seconds(PROGRAMS)
    return 1e3 * s / n if s > 0 else None
