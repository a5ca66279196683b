"""Host milliseconds per answer in store reads and their crc32c checks,
cache hits not included (the program span ``repro.store.read`` in the
segment fetcher; self time in the traced window, summed over threads)."""


def read(r):
    return r.span_ms_per_answer("repro.store.read")
