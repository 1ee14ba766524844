"""Reducers over counts the harness took or read from the program."""

from __future__ import annotations


def value(obs, cell, dev, key):
    return obs.get(key)


def ratio_pct(obs, cell, dev, num, den, den_scale=None):
    """100 * obs[num] / (obs[den] * obs[den_scale])."""
    d = obs.get(den) or 0
    if den_scale:
        d *= obs.get(den_scale) or 0
    return 100.0 * obs[num] / d if d else None
