"""Programs the backend was asked to compile inside the measured window
(cache hits count: a hit is still a program that was not warm). Expect 0."""


def read(run):
    return float(run["compiles"]["window"]["backend_compiles"])
