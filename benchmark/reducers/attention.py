"""The paged attention kernel's roofline in decode, from the program's own
count of the keys its ticks had to read.

A program whose layers are of two kinds (window and full attention) carries
``full_keys`` and ``window_keys`` on the device beside its expert counters
(``serve/paged_kv.py`` ``ATTENTION_COUNTERS``): for every decode tick, the sum
over decoding streams of ``len`` times the full layers and of ``min(len,
window)`` times the window layers.  The scheduler stamps them, cumulative, on
the same ``retire`` spans, so ``experts.traced_counters`` pairs their
difference over a stretch of the trace with the device time of that stretch.
The bytes are counted from keys (K and V of one token and layer:
``kv_row_bytes`` of the model's family), whatever implements the walk; a
kernel reads whole pages, so the share is a lower bound of what it moved.  A
program without the counters reads ``None`` and the metric is left out.
"""

from __future__ import annotations

from ..harness import common
from . import experts


def paged_roofline(obs, cell, dev, scope, module):
    """100 x (keys read between two stamps inside the trace x a key's K and
    V bytes, over the chip's peak bandwidth) over the device self time under
    ``scope`` inside the executions of ``module`` between the same stamps."""
    found = experts.traced_counters(obs, cell)
    row_bytes = getattr(cell["model"]["family"], "kv_row_bytes", None)
    if not found or row_bytes is None:
        return None
    delta, (t0, t1) = found
    keys = delta.get("full_keys", 0) + delta.get("window_keys", 0)
    ms = experts.scope_ms_between(obs, scope, module, t0, t1)
    if not keys or not ms:
        return None
    least_ms = (keys * row_bytes(cell["model"])
                / common.peaks(dev["kind"])["hbm_bytes_per_s"] * 1e3)
    return 100.0 * least_ms / ms
