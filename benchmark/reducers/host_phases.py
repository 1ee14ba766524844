"""Every idle gap of the device put down to a span of the program.

The program mirrors each of its host spans (``train/trace.py``: ``load``,
``dispatch``, ``fetch``, ``prefill``, ``decode/submit``, ...) into the
``jax.profiler`` capture as a ``TraceAnnotation`` named ``nnpt:<span>``, so the
host's phases and the device's operations lie on one clock in one
``.xplane.pb``.  This module reads those annotations from the host planes (its
own small parse: ``xplane.parse`` keeps the harness's ``bench:`` names only)
on the one thread line that runs the loop, takes the device's idle intervals
(the gaps between ``xplane.merged`` busy intervals), and shares each interval
out over the spans open during it; the deepest of them names each instant.

A program without the mirror (the parent of the PR that brought it) has no
``nnpt:`` event: every reducer reads ``None`` and the metric is left out.
"""

from __future__ import annotations

import re

from . import xplane

MARK = "nnpt:"
LOOP_SPANS = ("dispatch", "decode")     # what the loop's own thread holds
HOST_PLANE = re.compile(r"^/host:")


def parse_host(path) -> dict:
    """{(plane, line): [(span, start_ns, end_ns)]} of the ``nnpt:`` events of
    the host planes, the prefix taken off, in order of start."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not HOST_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            events = [(e.name[len(MARK):], int(e.start_ns),
                       int(e.start_ns) + int(e.duration_ns))
                      for e in line.events if e.name.startswith(MARK)]
            if events:
                out[(plane.name, line.name)] = sorted(
                    events, key=lambda e: (e[1], -e[2]))
    return out


def loop_line(lines: dict) -> list:
    """The events of the thread that runs the trainer's or the scheduler's
    loop: the line with the most ``dispatch`` / ``decode`` spans."""
    def weight(events):
        return sum(1 for n, _s, _e in events if n in LOOP_SPANS)
    best = max(lines.values(), key=weight, default=[])
    return best if weight(best) else []


def idle_intervals(device_ops) -> list:
    """[(start_ns, end_ns)] between the busy intervals of one chip."""
    busy = xplane.merged((s, s + d) for _n, s, d in device_ops)
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]


def segments(events) -> list:
    """The loop thread's time cut at every span's start and end:
    [(start_ns, end_ns, [spans open there, outermost first])], the stretches
    under no span left out.  ``events`` in order of start, outer before
    inner."""
    cuts = sorted({t for _n, s, e in events for t in (s, e)})
    out, open_, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(events) and events[i][1] <= a:
            open_.append(events[i])
            i += 1
        open_ = [ev for ev in open_ if ev[2] > a]
        if open_:
            out.append((a, b, [ev[0] for ev in open_]))
    return out


def attribute(events, gaps) -> dict:
    """Idle nanoseconds by span, each gap shared out over the spans it
    overlaps (a gap often outlasts several short spans): 'deepest' gives
    every instant to the innermost span open then, 'under' to every span open
    then; key None holds the idle time under no span."""
    deepest, under = {}, {}
    segs, j = segments(events), 0
    for s, e in sorted(gaps):
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        named, k = 0, j
        while k < len(segs) and segs[k][0] < e:
            a, b, names = segs[k]
            ns = min(b, e) - max(a, s)
            named += ns
            deepest[names[-1]] = deepest.get(names[-1], 0) + ns
            for name in set(names):
                under[name] = under.get(name, 0) + ns
            k += 1
        for table in (deepest, under):
            table[None] = table.get(None, 0) + (e - s) - named
    return {"deepest": deepest, "under": under}


def trace_of(obs):
    """{'events': the loop thread's spans, 'chips': [attribute(...) a chip]},
    read once a run; None without a trace."""
    if "_host_phases" not in obs:
        device = xplane.trace_of(obs)
        path = obs["profiler"].trace_file() if obs.get("profiler") else None
        if not device or not device["devices"] or not path:
            obs["_host_phases"] = None
        else:
            events = loop_line(parse_host(path))
            obs["_host_phases"] = {
                "events": events,
                "chips": [attribute(events, idle_intervals(dev["ops"]))
                          for _p, dev in sorted(device["devices"].items())]}
    return obs["_host_phases"]


# ---- reducers (obs, cell, dev, **args) -> value or None ---------------------

def idle_ms_per(obs, cell, dev, span, per):
    """Idle milliseconds of the device under ``span`` (its child spans
    included), per occurrence of the span ``per`` in the traced window, mean
    over chips.  ``span`` None: idle under no span of the program.  0.0 when
    the span is in the trace and no idle falls under it; None when ``per`` or
    ``span`` is in the trace nowhere."""
    t = trace_of(obs)
    if not t:
        return None
    names = [n for n, _s, _e in t["events"]]
    count = names.count(per)
    if not count or (span is not None and span not in names):
        return None
    ns = [chip["under"].get(span, 0) for chip in t["chips"]]
    return sum(ns) / len(ns) / 1e6 / count


def by_span(obs) -> dict | None:
    """First chip's idle seconds by the deepest span over each gap (key
    "(none)" for gaps under no span).  For PERF.md; no metric reads it."""
    t = trace_of(obs)
    if not t or not t["events"]:
        return None
    return {k or "(none)": v / 1e9 for k, v in sorted(
        t["chips"][0]["deepest"].items(), key=lambda kv: -kv[1])}
