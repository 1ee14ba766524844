"""Operations and bytes, counted from shapes by the benchmark (never by the
program's own ``fwd_flops``), and the shares of the chip's peak they give.

The formulas' outer structure is here and the same for every model; the terms
that depend on the kind of block (which parameters meet every token in a
matrix product, what attention costs at a context, which weights a decode tick
reads, what a cached token holds) are its family's, reached through the model
dict (``model["family"]``, ``benchmark/families/<family>.py``)."""

from __future__ import annotations

import numpy as np

from ..harness import common

BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def dtype_bytes(dtype: str) -> int:
    if dtype not in BYTES:
        raise ValueError(f"dtype {dtype!r} has no byte count in counts.BYTES "
                         f"({', '.join(BYTES)})")
    return BYTES[dtype]


def matmul_params(model: dict) -> int:
    """Parameters that take part in a matrix product for every token."""
    return model["family"].matmul_params(model)


def forward_flops_per_token(model: dict, context: float) -> float:
    """One token's forward pass attending ``context`` keys: 2 per
    multiply-add in the projections, and scores plus values in attention."""
    return (2.0 * matmul_params(model)
            + model["family"].attention_flops(model, context))


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """Forward and backward (2x the forward) of a causal sequence of
    ``seq_len``; attention is counted at the full square, the convention of
    the 6N + 12 L d T formula.  Recomputed operations do not count."""
    return 3.0 * forward_flops_per_token(model, seq_len)


def request_flops(model: dict, prompt: int, output: int) -> float:
    """Prefill of ``prompt`` tokens (causal: half the square) and ``output``
    decoded tokens, each attending everything before it."""
    n = prompt + output
    attn = model["family"].attention_flops(model, np.arange(1, n + 1)).sum()
    return 2.0 * matmul_params(model) * n + float(attn)


def weight_bytes(model: dict, obs=None) -> int:
    """Bytes a decode tick has to read of the weights; ``obs`` is what the
    harness observed in the window, for a family whose ticks read only the
    weights their tokens reached."""
    return model["family"].decode_weight_bytes(model, obs)


def kv_bytes_per_token(model: dict) -> int:
    """Bytes of cache one token holds, all layers."""
    return model["family"].cache_bytes_per_token(model)


# ---- reducers (obs, cell, dev, **args) -> value or None ---------------------

def mfu_train(obs, cell, dev):
    peak = common.peaks(dev["kind"])["bf16_flops"] * dev["count"]
    per_s = obs["tokens"] / obs["window_s"]
    return 100.0 * per_s * train_flops_per_token(
        cell["model"], cell["job"]["seq_len"]) / peak


def mfu_serve(obs, cell, dev):
    if not obs["begun_sizes"]:
        return None
    peak = common.peaks(dev["kind"])["bf16_flops"] * dev["count"]
    flops = sum(request_flops(cell["model"], p, n)
                for p, n in obs["begun_sizes"])
    return 100.0 * flops / obs["window_s"] / peak


def decode_hbm_share(obs, cell, dev, module):
    """Bytes one decode tick must read (weights once, the live cache of the
    decoding streams) over the peak bandwidth, against the tick's measured
    device time."""
    from . import xplane

    ms = xplane.module_median_ms(obs, cell, dev, module=module)
    if ms is None or not obs["decode_ticks"]:
        return None
    live = obs["attended_keys"] / obs["decode_ticks"]
    need = (weight_bytes(cell["model"], obs)
            + live * kv_bytes_per_token(cell["model"]))
    least_ms = need / common.peaks(dev["kind"])["hbm_bytes_per_s"] * 1e3
    return 100.0 * least_ms / ms
