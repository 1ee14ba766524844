"""The plain reference: one pre-LN decoder block in float32 ``jax.numpy``.

No cache, no kernels, no batching tricks, nothing imported from the program.
It covers the options the benchmark's configurations use: learned positions or
rotary ones, multi-head or grouped-query attention, an ungated tanh-GELU
feed-forward, biased LayerNorm and biased projections, an untied head.  Every
caller runs it under ``jax.default_matmul_precision("highest")``: on a TPU a
float32 product is otherwise made in lower precision.

``quant`` is the control's hook and no part of the reference proper: a function
applied to both operands of every projection, with which the control computes
the same pass in the nearest precision below the configuration's.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def layer_norm(x, scale, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def rope(x, positions, theta):
    """Rotate (B, T, H, D) by absolute positions (T,), halves [x1 | x2]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * freqs[None, :]       # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def dense(x, w, b, quant=None):
    if quant is not None:
        x, w = quant(x), quant(w)
    return x @ w + b


def attention(model, q, k, v):
    """Causal softmax attention; q (B, T, H, D), k and v (B, T, KV, D)."""
    groups = model["n_heads"] // model["n_kv_heads"]
    k, v = jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2)
    t = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def block(model, p, x, quant=None):
    """x + Attn(LN(x)), then x + FFN(LN(x)); ``p`` is one layer's tensors by
    the names of ``weights.LAYER``, already float32."""
    b, t, d = x.shape
    h, kv = model["n_heads"], model["n_kv_heads"]
    hd = d // h
    y = layer_norm(x, p["ln1.scale"], p["ln1.bias"], model["ln_eps"])
    qkv = dense(y, p["qkv.w"], p["qkv.b"], quant)
    q = qkv[..., :d].reshape(b, t, h, hd)
    k = qkv[..., d:d + kv * hd].reshape(b, t, kv, hd)
    v = qkv[..., d + kv * hd:].reshape(b, t, kv, hd)
    if model["pos_encoding"] == "rope":
        pos = jnp.arange(t)
        q, k = rope(q, pos, model["rope_theta"]), rope(k, pos,
                                                       model["rope_theta"])
    a = attention(model, q, k, v).reshape(b, t, d)
    x = x + dense(a, p["attn_out.w"], p["attn_out.b"], quant)
    y = layer_norm(x, p["ln2.scale"], p["ln2.bias"], model["ln_eps"])
    y = gelu_tanh(dense(y, p["ff_in.w"], p["ff_in.b"], quant))
    return x + dense(y, p["ff_out.w"], p["ff_out.b"], quant)


def embed(model, outer, ids):
    x = outer["embed"].astype(F32)[ids]
    if model["pos_encoding"] == "learned":
        x = x + outer["pos"].astype(F32)[:ids.shape[1]][None]
    return x


def head_logits(model, outer, x, quant=None):
    y = layer_norm(x, outer["ln_f.scale"].astype(F32),
                   outer["ln_f.bias"].astype(F32), model["ln_eps"])
    return dense(y, outer["head.w"].astype(F32), 0.0, quant)


def fp8_cast(x):
    """Round to float8 e4m3 under a per-tensor scale, as fp8 inference does
    (the control for a bfloat16 configuration)."""
    amax = jnp.maximum(jnp.abs(x).max(), 1e-30)
    scale = amax / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
