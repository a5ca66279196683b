"""Device milliseconds of the fused plane decode programs
(``kernels/ops.py`` ``_decode_fused``, ``_decode_fused_batch``) per answer
of the traced window."""

PROGRAMS = r"_decode_fused"


def read(r):
    n = len(r.answers)
    if r.trace is None or not n:
        return None
    s = r.trace.program_seconds(PROGRAMS)
    return 1e3 * s / n if s > 0 else None
