"""Seconds from the process's start to the window's: device checks,
generating and refactoring the fields, warm-up and every compile."""


def read(r):
    return r.setup_s
