"""Host milliseconds per answer in the QoI estimate: upload, dispatch and
bounds, less the wait and copy spans inside it (the program span
``repro.retrieval.estimate``; self time in the traced window, summed over
threads)."""


def read(r):
    return r.span_ms_per_answer("repro.retrieval.estimate")
