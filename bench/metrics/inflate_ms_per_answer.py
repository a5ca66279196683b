"""Host milliseconds per answer in the host entropy decode of fetched
planes (the program span ``repro.codec.inflate`` in
``LevelStream.fetch_to_planes``; self time in the traced window, summed
over threads)."""


def read(r):
    return r.span_ms_per_answer("repro.codec.inflate")
