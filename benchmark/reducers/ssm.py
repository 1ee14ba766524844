"""The two rooflines of a state-space mixer's recurrence, from the program's
own counts of what its ticks and chunks ran.

A program with recurrent state carries ``ssm_state_updates`` (decoding streams
x mixer layers, a decode tick) and ``ssm_prefill_tokens`` (true prompt columns
x mixer layers, a prefill chunk) on the device (``serve/paged_kv.py``
``SSM_COUNTERS``) beside ``decode_ticks_counted`` and
``prefill_chunks_counted``; the scheduler stamps them, cumulative, on its
``retire`` spans, so ``experts.traced_counters`` pairs their difference over a
stretch of the trace with the device time of that stretch
(``experts.scope_ms_between``), as ``reducers/attention.py`` does.  Bytes and
operations are counted from shapes and those counters, whatever implements the
scopes (the family's ``state_bytes`` and ``recurrence_flops``), so both shares
are lower bounds of what was moved and cannot pass 100 %.  A program without
the counters reads ``None`` and the metric is left out.
"""

from __future__ import annotations

from ..harness import common
from . import counts, experts


def _stretch(obs, cell, counter, scope, module):
    """(the counters' difference between two stamps inside the trace, the
    device self milliseconds under ``scope`` in ``module`` between them), or
    None where the family, the counter or the time is missing."""
    fam = cell["model"]["family"]
    found = experts.traced_counters(obs, cell)
    if not found or not hasattr(fam, "state_bytes") \
            or not found[0].get(counter):
        return None
    delta, (t0, t1) = found
    ms = experts.scope_ms_between(obs, scope, module, t0, t1)
    return (delta, ms) if ms else None


def state_roofline(obs, cell, dev, scope, module):
    """Decode: every state row updated between two stamps is read once and
    written once (``ssm_state_updates`` x 2 x the family's ``state_bytes``),
    over the chip's peak bandwidth, against the device self time under
    ``scope`` inside the executions of ``module`` in that same stretch."""
    got = _stretch(obs, cell, "ssm_state_updates", scope, module)
    if got is None:
        return None
    delta, ms = got
    model = cell["model"]
    need = delta["ssm_state_updates"] * 2.0 * model["family"].state_bytes(
        model)
    least_ms = need / common.peaks(dev["kind"])["hbm_bytes_per_s"] * 1e3
    return 100.0 * least_ms / ms


def scan_bytes(model: dict, tokens: float, chunks: float) -> float:
    """Bytes the recurrence of the prefill chunks must move, all layers
    (``tokens`` and ``chunks`` already count them): a true token's inputs
    ``xs``, ``B``, ``C`` in the compute type, its ``dt`` and its output in
    float32; the state read and written once a chunk a layer."""
    fam = model["family"]
    width = counts.dtype_bytes(model["compute_dtype"])
    d_ssm = fam.d_ssm(model)
    per_token = ((d_ssm + 2 * model["ssm_groups"] * model["ssm_state"])
                 * width + 4 * (model["ssm_heads"] + d_ssm))
    return tokens * per_token + chunks * 2.0 * fam.state_bytes(model)


def scan_roofline(obs, cell, dev, scope, module):
    """Prefill: the larger of the recurrence's operations
    (``ssm_prefill_tokens`` x the family's ``recurrence_flops``) over the
    chip's peak and of the bytes it must move (:func:`scan_bytes`) over the
    peak bandwidth, against the device self time under ``scope`` inside the
    executions of ``module`` between the same two stamps."""
    got = _stretch(obs, cell, "ssm_prefill_tokens", scope, module)
    if got is None:
        return None
    delta, ms = got
    model, peaks = cell["model"], common.peaks(dev["kind"])
    tokens = delta["ssm_prefill_tokens"]
    chunks = delta.get("prefill_chunks_counted", 0) * model["n_layers"]
    least_ms = 1e3 * max(
        tokens * model["family"].recurrence_flops(model)
        / peaks["bf16_flops"],
        scan_bytes(model, tokens, chunks) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_ms / ms
