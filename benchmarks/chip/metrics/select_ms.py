"""Milliseconds per round of the client EF and selection: the Pallas kernel topk_ef_sparse, or any operation under the scope ef_select: the summed device durations of its
events in the traced window over the rounds traced, averaged over chips."""

PATTERN, SCOPE = r"^topk_ef_sparse$", "ef_select"


def read(r):
    if r.trace is None or not r.rounds or not r.trace.chips:
        return None
    per = [r.trace.sum_s(c, PATTERN, SCOPE) for c in r.trace.chips]
    if not any(per):
        return None
    return 1e3 * sum(per) / len(per) / r.rounds
