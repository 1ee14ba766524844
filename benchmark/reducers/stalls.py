"""Reducers over the program's ``stall`` spans (``train/trace.py`` "Laps and
stalls": one span a tick or step that ran long, over the lap's whole length,
with the loop's name, the seconds over the median lap and where it stood), as
the harness's listener recorded them inside the window.

A lap in which the harness started or stopped its own profiler is the
harness's, not the program's, and is left out: the ``--trace 1`` run would
read its own capture otherwise.  A program that records no such span (the
parent of the PR that brought them) reads 0.0, like a window without a stall.
"""

from __future__ import annotations


def _stalls(obs, loop):
    """(wall s, excess s) of the window's stalls of ``loop``; None for a
    window with no span at all."""
    if not obs["spans"]:
        return None
    profiler = obs.get("profiler")
    marks = [t for t in (getattr(profiler, "t_start", None),
                         getattr(profiler, "t_stop", None)) if t is not None]
    out = []
    for name, _t, dur, attrs in obs["spans"]:
        if name != "stall" or attrs.get("loop") != loop:
            continue
        t0 = attrs.get("t_perf")        # perf_counter, the profiler's clock
        if t0 is not None and any(t0 <= m < t0 + dur for m in marks):
            continue
        out.append((dur, attrs["excess_s"]))
    return out


def per_second(obs, cell, dev, loop):
    """Milliseconds the loop's stalls ran over the median lap, per second of
    the window."""
    stalls = _stalls(obs, loop)
    if stalls is None:
        return None
    return 1e3 * sum(excess for _dur, excess in stalls) / obs["window_s"]


def longest_ms(obs, cell, dev, loop):
    """The longest stalled lap's wall time."""
    stalls = _stalls(obs, loop)
    if stalls is None:
        return None
    return 1e3 * max((dur for dur, _excess in stalls), default=0.0)
