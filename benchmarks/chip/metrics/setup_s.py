"""Seconds from process start to the window's first round: chips,
compile or cache load, state made from the seed, the checked rounds and
the window's batches."""


def read(r):
    return r.setup_s
