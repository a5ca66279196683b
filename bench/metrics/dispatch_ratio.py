"""Decode batcher: (decode + recompose items) / device dispatches."""


def read(r):
    c = r.counters
    disp = c.get("batch_decode_dispatches", 0.0) \
        + c.get("batch_recompose_dispatches", 0.0)
    if not disp:
        return None
    return (c["batch_decode_items"] + c["batch_recompose_items"]) / disp
