"""Milliseconds per round of the server ingest and FedAMS step: the Pallas kernel fedams_ingest, or any operation under the scope server_ingest: the summed device durations of its
events in the traced window over the rounds traced, averaged over chips."""

PATTERN, SCOPE = r"^fedams_ingest$", "server_ingest"


def read(r):
    if r.trace is None or not r.rounds or not r.trace.chips:
        return None
    per = [r.trace.sum_s(c, PATTERN, SCOPE) for c in r.trace.chips]
    if not any(per):
        return None
    return 1e3 * sum(per) / len(per) / r.rounds
