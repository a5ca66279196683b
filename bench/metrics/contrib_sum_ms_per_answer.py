"""Host milliseconds per answer in the reader's contribution rebuild and
fixed-order sum, less the decode, wait and copy spans inside it (the
program span ``repro.reader.refresh``; self time in the traced window,
summed over threads)."""


def read(r):
    return r.span_ms_per_answer("repro.reader.refresh")
