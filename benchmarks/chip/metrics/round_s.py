"""Seconds per federated round: the measured window (first round's batch
staged to the last round's loss read back) over the rounds completed in
it. A stall counts, since it is in the window."""


def read(r):
    return r.window_s / r.rounds if r.rounds else None
