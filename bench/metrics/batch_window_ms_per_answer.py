"""Host milliseconds per answer spent waiting for a decode batch to fill
or drain (the program span ``repro.batch.window`` in ``Ticket.result``;
self time in the traced window, summed over threads)."""


def read(r):
    return r.span_ms_per_answer("repro.batch.window")
