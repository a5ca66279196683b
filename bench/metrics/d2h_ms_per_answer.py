"""Host milliseconds per answer in the device-to-host copies themselves
(the program span ``repro.transfer.d2h`` in ``trace.to_host``; self time
in the traced window, summed over threads)."""


def read(r):
    return r.span_ms_per_answer("repro.transfer.d2h")
