"""Reducers over the expert-load counters of a program that routes without
drops, and the two rooflines of its grouped product.

The program carries the counters on the device and its scheduler stamps them,
cumulative, on every ``retire`` span (``serve/paged_kv.py``
``EXPERT_COUNTERS``); the harness's span listener keeps the spans, so the
counters reach ``obs["spans"]`` with no edit to the harness.  A window's
numbers are the last reading minus the first (the model's family reads them:
``expert_counters``); the two rooflines pair the counters of a stretch of the
trace with the device time of that same stretch (``traced_counters``).  Bytes and operations are counted from shapes and from
those counters, whatever implements the product.  A program without the
counters (the parent of the PR that brought them) reads ``None`` everywhere.
"""

from __future__ import annotations

import bisect
import re

from ..harness import common
from . import counts, host_phases, scopes, xplane


def window_counters(obs, cell):
    fam = cell["model"]["family"]
    read = getattr(fam, "expert_counters", None)
    return read(obs) if read else None


def traced_counters(obs, cell):
    """The counters over a stretch of the TRACE: (last stamped ``retire``
    inside the trace minus the first, their start times on the trace's
    clock), or None.  A stamp is exact as of its tick (the fetch that
    brought it waited for the tick's program), so the device operations
    that start between the two ``retire`` annotations are exactly the ones
    the difference counts.  The trace's ``nnpt:retire`` annotations are
    matched to the listener's ``retire`` spans as the run of spans whose
    start times differ from the annotations' by one constant."""
    if "_traced_counters" in obs:
        return obs["_traced_counters"]
    obs["_traced_counters"] = found = None
    phases = host_phases.trace_of(obs)
    spans = [(t, a) for n, t, _d, a in obs.get("spans", ()) if n == "retire"]
    marks = [s for n, s, _e in (phases or {}).get("events", ())
             if n == "retire"]
    if len(marks) >= 2 and len(spans) >= len(marks):
        def spread(k):
            off = [m - spans[k + i][0] * 1e9 for i, m in enumerate(marks)]
            return max(off) - min(off)
        k = min(range(len(spans) - len(marks) + 1), key=spread)
        if spread(k) < 5e6:         # 5 ms: far under a tick, over any jitter
            fresh = [(marks[i], spans[k + i][1]) for i in range(len(marks))
                     if "decode_ticks_counted" in spans[k + i][1]]
            if len(fresh) >= 2:
                (t0, a), (t1, b) = fresh[0], fresh[-1]
                found = ({key: b[key] - a[key] for key in a
                          if key != "tick" and isinstance(a[key], int)},
                         (t0, t1))
    obs["_traced_counters"] = found
    return found


def scope_ms_between(obs, scope, module, t0, t1) -> float | None:
    """Device self milliseconds under ``scope`` of the operations that start
    in [t0, t1) inside executions of ``module`` (first chip)."""
    trace = scopes.trace_of(obs)
    if not trace:
        return None
    dev = trace[sorted(trace)[0]]
    rx, mod = scopes.component(scope), re.compile(module)
    runs = xplane.merged((s, s + d) for n, s, d in dev["modules"]
                         if mod.search(n))
    starts = [s for s, _e in runs]

    def inside(start):
        i = bisect.bisect_right(starts, start) - 1
        return i >= 0 and start < runs[i][1]

    ns = sum(own for path, start, own in dev["self"]
             if t0 <= start < t1 and rx.search(path) and inside(start))
    return ns / 1e6 or None


def _expert_bytes(model) -> float:
    return (model["family"].expert_params(model)
            * counts.dtype_bytes(model["param_dtype"]))


# ---- reducers (obs, cell, dev, **args) -> value or None ---------------------

def load_max_over_mean(obs, cell, dev):
    """The busiest held expert's tokens over the mean expert's, each summed
    over every program and layer of the window: 1 is an even load."""
    c = window_counters(obs, cell)
    if not c or not c["expert_assignments"]:
        return None
    mean = c["expert_assignments"] / cell["model"]["experts_held"]
    return c["expert_tokens_max"] / mean


def reached_share(obs, cell, dev):
    """Held experts that got at least one token in a decode tick, over all
    the held experts of all layers."""
    c = window_counters(obs, cell)
    if not c or not c["decode_ticks_counted"]:
        return None
    model = cell["model"]
    return 100.0 * c["experts_reached"] / (
        c["decode_ticks_counted"] * model["n_layers"]
        * model["experts_held"])


def _roofline(obs, cell, dev, scope, module, counter, per_count, peak):
    """100 x (``counter``'s count over a stretch of the trace x
    ``per_count`` units of work, over the chip's ``peak`` units a second)
    over the device time under ``scope`` inside the executions of ``module``
    in that same stretch."""
    found = traced_counters(obs, cell)
    if not found or not found[0].get(counter):
        return None
    delta, (t0, t1) = found
    ms = scope_ms_between(obs, scope, module, t0, t1)
    if not ms:
        return None
    least_ms = (delta[counter] * per_count
                / common.peaks(dev["kind"])[peak] * 1e3)
    return 100.0 * least_ms / ms


def hbm_share(obs, cell, dev, scope, module):
    """The grouped product's roofline in decode: the weights of the held
    experts that the decode ticks reached (every layer of every tick between
    two stamps inside the trace) over the peak bandwidth, against the device
    time under ``scope`` inside the executions of ``module`` between the
    same two stamps."""
    return _roofline(obs, cell, dev, scope, module, "experts_reached",
                     _expert_bytes(cell["model"]), "hbm_bytes_per_s")


def mxu_share(obs, cell, dev, scope, module):
    """The grouped product's roofline in prefill: 2 operations a parameter
    for every assignment that fell on a held expert in the prefill chunks
    between two stamps inside the trace, over the peak, against the device
    time under ``scope`` inside the executions of ``module`` between the
    same two stamps."""
    model = cell["model"]
    return _roofline(obs, cell, dev, scope, module,
                     "prefill_expert_assignments",
                     2.0 * model["family"].expert_params(model), "bf16_flops")
