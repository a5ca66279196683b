"""Share of the clients' waiting spent outside the server's handler (in
the serve plane's queue and session lock): sum(client time - reply
latency_s) / sum(client time), in %."""


def read(r):
    done = [a for a in r.answers if a.error is None]
    total = sum(a.wait_s for a in done)
    if total <= 0:
        return None
    return 100.0 * sum(max(0.0, a.wait_s - a.latency_s) for a in done) / total
