"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: each chip's device operations, the union of their intervals
(busy time), sums of the operations a metric matches, the part of those
that no other operation overlaps (exposed time), and the longest idle
gaps labelled with the harness's host span at that moment.

The trace is read with ``jax.profiler.ProfileData``. A chip is a plane
named ``/device:TPU:<i>``; its operations are the events of its
``XLA Ops`` line, named by their HLO instruction (``%topk_ef_sparse.17 =
... custom-call(...)``), a Pallas kernel by the ``name`` of its
``pallas_call``. An operation that encloses others there (a ``while``
loop around its body's operations) is a container: only the operations
inside it count, so the time between them stays idle. An asynchronous
operation (a collective's start to its done) is an event of the chip's
``Async XLA Ops`` line; only metrics of collectives read those. Each
operation's scope path (its ``op_name``, where ``jax.named_scope`` puts
its names) comes from the compiled program's HLO text, by instruction
name. The harness's host spans (``stage``, ``dispatch``, ``sync``) are
events of the host plane; the traced window runs from the first span's
start to the last one's end.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE = re.compile(r"^/device:TPU:(\d+)$")
HOST_SPANS = ("stage", "dispatch", "sync")
OPS_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"
HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?'
                    r'metadata=\{[^}]*?op_name="([^"]*)"', re.M)


def op_scopes(hlo_text: str) -> dict:
    """``{instruction name: op_name}`` of a compiled program's HLO text."""
    return dict(HLO_OP.findall(hlo_text))


def short_name(name: str) -> str:
    """``%topk_ef_sparse.17 = (...) custom-call(...)`` -> ``topk_ef_sparse``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


@dataclasses.dataclass
class Op:
    name: str        # short name
    start: int       # ns
    end: int
    scope: str       # the instruction's op_name ("" where unknown)


def union(intervals):
    """Disjoint sorted intervals covering the given ``(start, end)`` pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def covered(merged, s, e) -> int:
    """Length of ``[s, e)`` that the disjoint sorted ``merged`` covers."""
    i = max(bisect.bisect_right([a for a, _ in merged], s) - 1, 0)
    tot = 0
    while i < len(merged) and merged[i][0] < e:
        a, b = merged[i]
        tot += max(0, min(b, e) - max(a, s))
        i += 1
    return tot


def leaves(events):
    """The events that enclose no other event (sorted by start)."""
    evs = sorted(events, key=lambda o: (o.start, -o.end))
    out = []
    for i, o in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt.start < o.end and nxt.end <= o.end:
            continue                     # a container of the next event
        out.append(o)
    return out


class Reduced:
    """One traced window: per chip, its leaf operations within the window."""

    def __init__(self, chips: dict, spans: list, window: tuple,
                 asyncs: dict | None = None):
        self.chips = chips                # chip index -> [Op]
        self.asyncs = asyncs or {}        # chip index -> [Op], async spans
        self.spans = spans                # [(name, start, end)] host spans
        self.t0, self.t1 = window
        self.window_s = (self.t1 - self.t0) / 1e9
        self.merged = {c: union((o.start, o.end) for o in ops)
                       for c, ops in chips.items()}

    def busy_s(self) -> float:
        """Busy seconds, averaged over the chips."""
        if not self.chips:
            return 0.0
        return sum(sum(b - a for a, b in m) for m in self.merged.values()) \
            / len(self.merged) / 1e9

    def matching(self, chip: int, pattern: str, scope: str = "",
                 with_async: bool = False):
        rx = re.compile(pattern)
        ops = self.chips.get(chip, []) + (self.asyncs.get(chip, [])
                                  if with_async else [])
        return [o for o in ops
                if rx.search(o.name) or (scope and scope in o.scope)]

    def sum_s(self, chip: int, pattern: str, scope: str = "",
              with_async: bool = False) -> float:
        """Summed seconds of the matched operations (their union where
        asynchronous spans are included, which overlap their own ends)."""
        hit = self.matching(chip, pattern, scope, with_async)
        if with_async:
            return sum(b - a for a, b in
                       union((o.start, o.end) for o in hit)) / 1e9
        return sum(o.end - o.start for o in hit) / 1e9

    def exposed_s(self, chip: int, pattern: str, scope: str = "",
                  with_async: bool = False) -> float:
        """Seconds of the matched operations' union that no other operation
        of that chip overlaps."""
        hit = self.matching(chip, pattern, scope, with_async)
        ids = {id(o) for o in hit}
        others = union((o.start, o.end) for o in self.chips.get(chip, [])
                       if id(o) not in ids)
        return sum((b - a) - covered(others, a, b)
                   for a, b in union((o.start, o.end) for o in hit)) / 1e9

    def span_at(self, t: int) -> str:
        for name, s, e in self.spans:
            if s <= t < e:
                return name
        return "host"

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (chip 0, by short
        name) and the longest idle gaps, each named by the host span
        around the gap's middle."""
        if not self.chips:
            return {"device_ops": [], "idle_gaps": []}
        c = min(self.chips)
        tot = {}
        for o in self.chips[c]:
            tot[o.name] = tot.get(o.name, 0) + (o.end - o.start)
        ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        m = self.merged[c]
        bounds = [self.t0] + [x for ab in m for x in ab] + [self.t1]
        gaps = [(bounds[i], bounds[i + 1])
                for i in range(0, len(bounds) - 1, 2)
                if bounds[i + 1] > bounds[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return {"device_ops": [[n, t / 1e9] for n, t in ops],
                "idle_gaps": [[self.span_at((a + b) // 2), (b - a) / 1e9]
                              for a, b in gaps[:top]]}


def _ops(events, scopes: dict) -> list:
    out = []
    for e in events:
        head = e.name.split(" = ", 1)[0].lstrip("%")
        out.append(Op(short_name(e.name), int(e.start_ns),
                      int(e.start_ns + e.duration_ns), scopes.get(head, "")))
    return out


def load(trace_dir: str, chips: int, scopes: dict | None = None) -> Reduced:
    """Reduce the trace written under ``trace_dir`` (chips ``0..chips-1``);
    ``scopes`` is :func:`op_scopes` of the traced program."""
    import jax
    scopes = scopes or {}
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    spans, raw, asyncs = [], {}, {}
    for f in files:
        pd = jax.profiler.ProfileData.from_file(f)
        for plane in pd.planes:
            dev = DEVICE.match(plane.name)
            chip = int(dev.group(1)) if dev else None
            for line in plane.lines:
                if dev and chip < chips and line.name == OPS_LINE:
                    raw.setdefault(chip, []).extend(_ops(line.events, scopes))
                elif dev and chip < chips and line.name == ASYNC_LINE:
                    asyncs.setdefault(chip, []).extend(
                        _ops(line.events, scopes))
                elif not dev:
                    spans.extend((e.name, int(e.start_ns),
                                  int(e.start_ns + e.duration_ns))
                                 for e in line.events
                                 if e.name in HOST_SPANS)
    spans.sort(key=lambda s: s[1])
    if spans:
        window = (spans[0][1], spans[-1][2])
    else:
        ends = [(o.start, o.end) for ops in raw.values() for o in ops]
        window = (min(s for s, _ in ends), max(e for _, e in ends)) \
            if ends else (0, 0)

    def clip(ops):
        keep = [o for o in ops if o.end > window[0] and o.start < window[1]]
        for o in keep:
            o.start, o.end = max(o.start, window[0]), min(o.end, window[1])
        return keep

    return Reduced({c: clip(leaves(ops)) for c, ops in raw.items()}, spans,
                   window, {c: clip(ops) for c, ops in asyncs.items()})
