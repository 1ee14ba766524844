"""Operations and bytes, counted from shapes by the benchmark (never by the
program's own ``fwd_flops``), and the shares of the chip's peak they give."""

from __future__ import annotations

from ..harness import common, weights

BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def matmul_params(model: dict) -> int:
    """Parameters that take part in a matrix product for every token: the
    layers' four projections and the head (not the embedding tables, which
    are looked up, nor norms and biases)."""
    s = weights.shapes(model)
    per_layer = sum(s[n][0] * s[n][1]
                    for n in ("qkv.w", "attn_out.w", "ff_in.w", "ff_out.w"))
    return model["n_layers"] * per_layer + s["head.w"][0] * s["head.w"][1]


def forward_flops_per_token(model: dict, context: float) -> float:
    """One token's forward pass attending ``context`` keys: 2 per
    multiply-add in the projections, and scores plus values in attention."""
    attn = 4.0 * model["n_layers"] * model["d_model"] * context
    return 2.0 * matmul_params(model) + attn


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """Forward and backward (2x the forward) of a causal sequence of
    ``seq_len``; attention is counted at the full square, the convention of
    the 6N + 12 L d T formula.  Recomputed operations do not count."""
    return 3.0 * forward_flops_per_token(model, seq_len)


def request_flops(model: dict, prompt: int, output: int) -> float:
    """Prefill of ``prompt`` tokens (causal: half the square) and ``output``
    decoded tokens, each attending everything before it."""
    n = prompt + output
    attn = 4.0 * model["n_layers"] * model["d_model"] * (n * (n + 1) / 2.0)
    return 2.0 * matmul_params(model) * n + attn


def weight_bytes(model: dict) -> int:
    """Bytes a decode tick has to read of the weights: everything but the
    embedding tables (of which it reads one row a stream)."""
    s = weights.shapes(model)
    tables = s["embed"][0] * s["embed"][1] + (
        s["pos"][0] * s["pos"][1] if "pos" in s else 0)
    return (weights.n_params(model) - tables) * BYTES[model["param_dtype"]]


def kv_bytes_per_token(model: dict) -> int:
    hd = model["d_model"] // model["n_heads"]
    return (2 * model["n_layers"] * model["n_kv_heads"] * hd
            * BYTES[model["compute_dtype"]])


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
    """Bytes one decode tick must read (weights once, live K and V of the
    decoding streams) over the peak bandwidth, against the tick's measured
    device time."""
    from . import xplane

    ms = xplane.module_median_ms(obs, cell, dev, module=module)
    if ms is None or not obs["decode_ticks"]:
        return None
    live = obs["attended_keys"] / obs["decode_ticks"]
    need = (weight_bytes(cell["model"])
            + live * kv_bytes_per_token(cell["model"]))
    least_ms = need / common.peaks(dev["kind"])["hbm_bytes_per_s"] * 1e3
    return 100.0 * least_ms / ms
