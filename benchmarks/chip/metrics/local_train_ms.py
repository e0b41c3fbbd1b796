"""Milliseconds per round of the clients' local training: the summed
device durations of the operations under the program's scope
``local_train`` (the local steps, forward and backward, and the delta
they leave) in the traced window over the rounds traced, averaged over
chips. A program without that scope reads nothing."""

NOTHING, SCOPE = r"(?!)", "local_train"


def read(r):
    if r.trace is None or not r.rounds or not r.trace.chips:
        return None
    per = [r.trace.sum_s(c, NOTHING, SCOPE) for c in r.trace.chips]
    if not any(per):
        return None
    return 1e3 * sum(per) / len(per) / r.rounds
