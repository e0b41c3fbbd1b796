"""Milliseconds per round of device operations under none of the
program's layer scopes (``local_train``, ``ef_select``, ``uplink``,
``server_ingest``): the summed durations in the traced window over the
rounds traced, averaged over chips. It guards that the layer map keeps
covering the round. A trace in which no operation carries any of the
four reads nothing, so a program without the layer map reads null rather
than the whole round."""

LAYERS = ("local_train", "ef_select", "uplink", "server_ingest")


def _scoped(op) -> bool:
    return any(name in op.scope for name in LAYERS)


def read(r):
    if r.trace is None or not r.rounds or not r.trace.chips:
        return None
    chips = r.trace.chips.values()
    if not any(_scoped(o) for ops in chips for o in ops):
        return None
    per = [sum(o.end - o.start for o in ops if not _scoped(o)) / 1e9
           for ops in chips]
    return 1e3 * sum(per) / len(per) / r.rounds
