"""Certified answers per second over the whole window, on the client's
clock: each client's certified answers over the time from the window's
start to its last answer (which it awaited past the window's end), summed
over the clients."""


def read(r):
    total = 0.0
    for answers in r.per_client().values():
        last = max(a.done for a in answers)
        if last > 0:
            total += sum(1 for a in answers if a.certified) / last
    return total
