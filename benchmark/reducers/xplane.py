"""From a ``jax.profiler`` trace (``*.xplane.pb``) to device metrics.

Read with ``jax.profiler.ProfileData`` and nothing else.  A TPU's plane is
named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per executed
HLO operation and ``XLA Modules`` one per executed program.  Busy time is the
union of the operation intervals, so nested operations (the body of a
``while``) are not counted twice.
"""

from __future__ import annotations

import re
import statistics

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_MARK = "bench:"


def short(name: str) -> str:
    """An operation's own name out of the HLO text the trace gives it
    (``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``)."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def family(name: str) -> str:
    """The kind of operation, for the breakdown: the name without its
    number, and a fusion's ``kind`` (``fusion.12 ... kind=kOutput`` ->
    ``fusion:Output``, which on a TPU is a fused matrix product)."""
    base = re.sub(r"[.\d]+$", "", short(name)) or short(name)
    kind = re.search(r"kind=k(\w+)", name)
    return f"{base}:{kind.group(1)}" if kind else base


def parse(path) -> dict:
    """{'devices': {plane: {'ops': [(name, start_ns, dur_ns)], 'modules':
    [...]}}, 'host': [(name, start_ns, dur_ns)]} — host events are the
    harness's own ``TraceAnnotation``s (names starting ``bench:``)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"ops": [], "modules": [], "families": {}}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if not key:
                    continue
                events = [(e.name, int(e.start_ns), int(e.duration_ns))
                          for e in line.events]
                dev[key] = [(short(n), s, d) for n, s, d in events]
                if key == "ops":
                    dev["families"] = self_time_by_family(events)
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [(e.name, int(e.start_ns), int(e.duration_ns))
                                for e in line.events
                                if e.name.startswith(HOST_MARK)]
    return out


def self_time_by_family(events) -> dict:
    """Device nanoseconds by family of operation, each instant given to the
    innermost operation running (a ``while`` holds its body's operations)."""
    total: dict = {}
    stack = []                      # (end, family, start of uncounted time)
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            end, fam, since = stack.pop()
            total[fam] = total.get(fam, 0) + end - since
            if stack:
                stack[-1][2] = end
        if stack:
            total[stack[-1][1]] = (total.get(stack[-1][1], 0)
                                   + s - stack[-1][2])
        stack.append([s + d, family(name), s])
    while stack:
        end, fam, since = stack.pop()
        total[fam] = total.get(fam, 0) + end - since
        if stack:
            stack[-1][2] = end
    return total


def merged(intervals):
    """Union of (start, end) intervals as a sorted list without overlaps."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_seconds(trace: dict) -> float:
    """Seconds in which an operation ran, averaged over the traced chips."""
    per = [sum(e - s for s, e in merged((s, s + d) for _, s, d in dev["ops"]))
           for dev in trace["devices"].values()]
    return sum(per) / len(per) / 1e9 if per else 0.0


def trace_of(obs):
    if "_trace" not in obs:
        path = obs["profiler"].trace_file() if obs.get("profiler") else None
        obs["_trace"] = parse(path) if path else None
    return obs["_trace"]


def traced_window_s(obs) -> float:
    p = obs["profiler"]
    return p.t_stop - p.t_start


# ---- reducers (obs, cell, dev, **args) -> value or None ---------------------

def idle_share(obs, cell, dev):
    t = trace_of(obs)
    if not t or not t["devices"]:
        return None
    return 100.0 * (1.0 - busy_seconds(t) / traced_window_s(obs))


def busy_ms_per_step(obs, cell, dev):
    t = trace_of(obs)
    if not t or not obs.get("traced_steps"):
        return None
    return 1e3 * busy_seconds(t) / obs["traced_steps"]


def op_ms_per_step(obs, cell, dev, pattern):
    """Device time of the operations whose name matches ``pattern``, per
    traced step and per chip."""
    t = trace_of(obs)
    if not t or not obs.get("traced_steps"):
        return None
    rx = re.compile(pattern)
    per = [sum(d for n, _s, d in devc["ops"] if rx.search(n))
           for devc in t["devices"].values()]
    if not any(per):
        return None
    return sum(per) / len(per) / 1e6 / obs["traced_steps"]


def module_median_ms(obs, cell, dev, module):
    """Median device time of the executions of the program whose module name
    matches ``module``."""
    t = trace_of(obs)
    if not t:
        return None
    rx = re.compile(module)
    durs = [d for devc in t["devices"].values()
            for n, _s, d in devc["modules"] if rx.search(n)]
    return statistics.median(durs) / 1e6 if durs else None


def breakdown(obs, top: int = 10) -> dict | None:
    """Where the first chip's time went, by family of operation (self time),
    and its longest idle gaps named by the harness's annotation over them."""
    t = trace_of(obs)
    if not t or not t["devices"]:
        return None
    devc = t["devices"][sorted(t["devices"])[0]]
    ops = sorted(devc["families"].items(), key=lambda kv: -kv[1])[:top]
    busy = merged((s, s + d) for _, s, d in devc["ops"])
    gaps = [(b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])]
    named = []
    for dur, s, e in sorted(gaps, reverse=True)[:top]:
        mid = (s + e) // 2
        host = [n for n, hs, hd in t["host"] if hs <= mid < hs + hd]
        named.append([host[0] if host else obs.get("gap_default", "host"),
                      dur / 1e9])
    return {"device_ops": [[n, d / 1e9] for n, d in ops], "idle_gaps": named}
