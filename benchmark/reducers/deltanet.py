"""The chunked delta rule's roofline, from the program's own counts of what
its prefill chunks ran.

A program with delta-rule layers carries ``ssm_prefill_tokens`` (true prompt
columns x layers that hold state, a prefill chunk) and
``prefill_chunks_counted`` on the device (``serve/paged_kv.py``
``SSM_COUNTERS``); the scheduler stamps them on its ``retire`` spans, and
``ssm._stretch`` pairs their difference over a stretch of the trace with the
device time under a scope in that stretch.  Bytes and operations are counted
from shapes and those counters, whatever implements the scope (the family's
``recurrence_flops``, ``state_bytes`` and ``n_linear``), so the share is a
lower bound of what was moved and cannot pass 100 %.  A program without the
counters reads ``None`` and the metric is left out.  The tick's roofline is
``ssm.state_roofline``'s: a state row read and written once an update.
"""

from __future__ import annotations

from ..harness import common
from . import counts, ssm


def scan_bytes(model: dict, tokens: float, chunks: float) -> float:
    """Bytes the chunked rule of the prefill chunks must move (``tokens``
    already counts the linear layers, ``chunks`` is chunks x linear layers):
    a true token's ``q``, ``k``, ``v`` in the compute type, its ``beta`` and
    decay in float32 and its output ``o`` in float32; the state read and
    written once a chunk a layer."""
    fam = model["family"]
    h, dk, dv = (fam.gdn_heads(model), model["gdn_key_dim"],
                 model["gdn_value_dim"])
    width = counts.dtype_bytes(model["compute_dtype"])
    per_token = (2 * h * dk + h * dv) * width + 4 * (2 * h + h * dv)
    return tokens * per_token + chunks * 2.0 * fam.state_bytes(model)


def scan_roofline(obs, cell, dev, scope, module):
    """Prefill: the larger of the rule's operations (``ssm_prefill_tokens``
    x the family's ``recurrence_flops``) over the chip's bf16 peak and of
    the bytes it must move (:func:`scan_bytes`) over the peak bandwidth,
    against the device self time under ``scope`` inside the executions of
    ``module`` between the same two stamps."""
    got = ssm._stretch(obs, cell, "ssm_prefill_tokens", scope, module)
    if got is None:
        return None
    delta, ms = got
    model, peaks = cell["model"], common.peaks(dev["kind"])
    fam = model["family"]
    if not hasattr(fam, "recurrence_flops") or not hasattr(fam, "n_linear"):
        return None
    tokens = delta["ssm_prefill_tokens"]
    chunks = delta.get("prefill_chunks_counted", 0) * fam.n_linear(model)
    least_ms = 1e3 * max(
        tokens * fam.recurrence_flops(model) / peaks["bf16_flops"],
        scan_bytes(model, tokens, chunks) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_ms / ms
