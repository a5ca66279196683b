"""Host milliseconds per answer spent waiting for device work before a
device-to-host copy (the program span ``repro.device.wait`` in
``trace.to_host``; self time in the traced window, summed over threads)."""


def read(r):
    return r.span_ms_per_answer("repro.device.wait")
