"""Device time by the program's own names: ``jax.named_scope`` paths.

The program wraps its work in named scopes (``attention``, ``chunked_ce``,
``optimizer_update``, ...; the table is in PERF.md section 3).  JAX writes the
scope path into every HLO instruction's ``metadata.op_name``
(``jit(shard_step)/shard_map/loss_and_grad/transpose(jvp(attention))/attn_dense/dot_general``)
and the TPU's trace carries it as the ``tf_op`` stat of the executed
operation's event *metadata* (the event's name is the HLO text, which holds no
metadata), where ``jax.profiler.ProfileData`` does not reach: ``xspace.py``
reads it.  A fusion carries its root's metadata, so attribution at a fusion's
border is approximate, and what the compiler adds itself (async copies and
slices) carries none; what no leaf scope holds is returned as unplaced.

Time is **self** time: each instant goes to the innermost operation running
(a ``while`` holds its body's operations), as ``xplane.self_time_by_family``
counts it.  A program with no such scope (the parent of the PR that brought
them) reads ``None`` everywhere, and the metric is left out of the line.
"""

from __future__ import annotations

import bisect
import re

from . import xplane, xspace

# scopes that name one piece of work and hold no other scope of this list,
# except ``attention``, which holds what implements it
LEAF_SCOPES = ("attention", "attn_proj", "ffn", "embed", "lm_head",
               "chunked_ce", "grad_exchange", "optimizer_update",
               "paged_scatter", "paged_gather", "attn_core", "sample")
_BACKWARD = "transpose("


def component(scope: str):
    """``scope`` as one component of an op_name path, bare or wrapped by a
    transformation (``jvp(scope)``, ``transpose(jvp(scope))``)."""
    return re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?:[)/]|$)")


def parse(path) -> dict:
    """{plane: {'ops': [(path, start_ns, dur_ns)], 'modules': [(name,
    start_ns, dur_ns)]}} of every device plane of the trace; ``path`` is ''
    for an operation that carries none."""
    space = xspace.read(path, xplane.DEVICE_PLANE,
                        lines=(xplane.OPS_LINE, xplane.MODULES_LINE))
    out = {}
    for plane, found in space.items():
        meta = found["metadata"]
        out[plane] = {
            "ops": [(meta[m].get("tf_op", ""), s, d)
                    for m, s, d in found["lines"].get(xplane.OPS_LINE, [])],
            "modules": [(meta[m]["name"], s, d) for m, s, d in
                        found["lines"].get(xplane.MODULES_LINE, [])]}
    return out


def self_times(ops) -> list:
    """[(path, start_ns, self_ns)] — each operation's own time, the time of
    the operations nested in it taken out."""
    out = []
    stack = []                      # [end, path, start, self so far, since]
    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, path, start, own, since = stack.pop()
            out.append((path, start, own + end - since))
            if stack:
                stack[-1][4] = end
    for path, s, d in sorted(ops, key=lambda e: (e[1], -e[2])):
        close(s)
        if stack:
            stack[-1][3] += s - stack[-1][4]
        stack.append([s + d, path, s, 0, s])
    close(float("inf"))
    return out


def trace_of(obs):
    """The parsed trace with self times, read once a run."""
    if "_scopes" not in obs:
        path = obs["profiler"].trace_file() if obs.get("profiler") else None
        trace = parse(path) if path else None
        for dev in (trace or {}).values():
            dev["self"] = self_times(dev["ops"])
        obs["_scopes"] = trace
    return obs["_scopes"]


def _mean_ns(trace, scope, keep) -> float | None:
    """Self nanoseconds under ``scope`` over the operations ``keep(plane,
    path, start)`` lets through, mean over chips; None where no chip ran an
    operation under the scope."""
    rx = component(scope)
    seen, per = False, []
    for plane, dev in trace.items():
        total = 0
        for path, start, own in dev["self"]:
            if rx.search(path):
                seen = True
                if keep(plane, path, start):
                    total += own
        per.append(total)
    return sum(per) / len(per) if seen and per else None


# ---- reducers (obs, cell, dev, **args) -> value or None ---------------------

def scope_ms_per_step(obs, cell, dev, scope, phase=None):
    """Device self time under ``scope`` per traced step and per chip;
    ``phase`` "fwd" or "bwd" keeps one pass (the backward pass's operations
    are the ones JAX names ``transpose(jvp(...))``)."""
    trace = trace_of(obs)
    if not trace or not obs.get("traced_steps"):
        return None
    if phase not in (None, "fwd", "bwd"):
        raise ValueError(f"phase must be fwd or bwd, got {phase!r}")
    keep = lambda _pl, path, _s: (                               # noqa: E731
        phase is None or (_BACKWARD in path) == (phase == "bwd"))
    ns = _mean_ns(trace, scope, keep)
    return None if ns is None else ns / 1e6 / obs["traced_steps"]


def scope_ms_per_module(obs, cell, dev, scope, module):
    """Device self time under ``scope`` inside the executions of the program
    whose ``XLA Modules`` name matches ``module``, per execution."""
    trace = trace_of(obs)
    if not trace:
        return None
    rx = re.compile(module)
    runs = {plane: xplane.merged((s, s + d) for n, s, d in dev["modules"]
                                 if rx.search(n))
            for plane, dev in trace.items()}
    count = sum(1 for dev in trace.values()
                for n, _s, _d in dev["modules"] if rx.search(n))
    if not count:
        return None

    starts = {plane: [s for s, _e in iv] for plane, iv in runs.items()}

    def inside(plane, _path, start):
        i = bisect.bisect_right(starts[plane], start) - 1
        return i >= 0 and start < runs[plane][i][1]

    ns = _mean_ns(trace, scope, inside)
    return None if ns is None else ns * len(trace) / 1e6 / count


def coverage(obs, top: int = 8) -> dict | None:
    """Where the first chip's busy time went by leaf scope (each operation
    under its innermost one), and what no leaf scope holds: the share, and
    the largest paths of it.  For PERF.md; no metric reads it."""
    trace = trace_of(obs)
    if not trace:
        return None
    dev = trace[sorted(trace)[0]]
    leaf = re.compile(r"(?:^|[/(])(" + "|".join(LEAF_SCOPES) + r")(?=[)/]|$)")
    by_scope, loose, busy = {}, {}, 0
    for path, _start, own in dev["self"]:
        busy += own
        hits = leaf.findall(path)
        if hits:
            key = hits[-1] + (":bwd" if _BACKWARD in path else "")
            by_scope[key] = by_scope.get(key, 0) + own
        else:
            key = re.sub(r"[.\d]+$", "", path) or "(no op_name)"
            loose[key] = loose.get(key, 0) + own
    unplaced = sum(loose.values())
    return {"busy_s": busy / 1e9,
            "by_scope_s": {k: v / 1e9 for k, v in
                           sorted(by_scope.items(), key=lambda kv: -kv[1])},
            "unplaced_s": unplaced / 1e9,
            "unplaced_share": unplaced / busy if busy else None,
            "unplaced_top": [[k, v / 1e9] for k, v in sorted(
                loose.items(), key=lambda kv: -kv[1])[:top]]}
