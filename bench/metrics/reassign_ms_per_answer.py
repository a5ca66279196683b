"""Host milliseconds per answer in the Alg-4 step: the ladder states, the
predicted-eps hints, the batched point estimate and the landing, less the
estimate span inside it (the program span ``repro.retrieval.reassign``;
self time in the traced window, summed over threads)."""


def read(r):
    return r.span_ms_per_answer("repro.retrieval.reassign")
