"""Reducers over the program's host spans (``train/trace.py``), as the
harness's listener recorded them inside the window."""

from __future__ import annotations


def _durations(obs, name):
    return [dur for n, _t, dur, _a in obs["spans"] if n == name]


def mean_ms(obs, cell, dev, span):
    durs = _durations(obs, span)
    return 1e3 * sum(durs) / len(durs) if durs else None


def share_of_window(obs, cell, dev, span):
    durs = _durations(obs, span)
    return 100.0 * sum(durs) / obs["window_s"] if durs else None
