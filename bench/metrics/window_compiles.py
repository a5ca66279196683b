"""Backend compiles (or loads from the persistent compilation cache) that
the window's server saw: the program's ``compiles_total``, counted from
the server's construction.  A warm run reads 0."""


def read(r):
    return r.counters.get("compiles_total")
