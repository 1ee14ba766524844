"""Seeded weights, made by the benchmark and by nothing else.

The program under test and the plain reference both get their weights from
here, so neither takes anything the other has made.  A ``Maker`` builds one
layer (or the embeddings, final norm and head) per jitted call on the device,
in the type the configuration stores them in: one call a layer, which is also
how the reference walks a model that does not fit the chip in float32.

Tensors are named flat: ``embed``, ``pos`` (learned positions only), ``ln_f.scale``,
``ln_f.bias``, ``head.w`` outside the layers and ``ln1.scale`` ... ``ff_out.b``
inside one.  Matrices are stored ``(in, out)``; the fused qkv projection is laid
out ``[q | k | v]`` with heads contiguous.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

OUTER = ("embed", "pos", "ln_f.scale", "ln_f.bias", "head.w")
LAYER = ("ln1.scale", "ln1.bias", "qkv.w", "qkv.b", "attn_out.w",
         "attn_out.b", "ln2.scale", "ln2.bias", "ff_in.w", "ff_in.b",
         "ff_out.w", "ff_out.b")


def shapes(model: dict) -> dict:
    """name -> shape for every tensor of one layer and of the outer part."""
    d, ff, v = model["d_model"], model["d_ff"], model["vocab_size"]
    hd = d // model["n_heads"]
    qkv = d + 2 * model["n_kv_heads"] * hd
    out = {"embed": (v, d), "ln_f.scale": (d,), "ln_f.bias": (d,),
           "head.w": (d, v),
           "ln1.scale": (d,), "ln1.bias": (d,), "qkv.w": (d, qkv),
           "qkv.b": (qkv,), "attn_out.w": (d, d), "attn_out.b": (d,),
           "ln2.scale": (d,), "ln2.bias": (d,), "ff_in.w": (d, ff),
           "ff_in.b": (ff,), "ff_out.w": (ff, d), "ff_out.b": (d,)}
    if model["pos_encoding"] == "learned":
        out["pos"] = (model["max_seq_len"], d)
    return out


def split_qkv(model: dict, x) -> dict:
    """The q, k and v columns of a fused qkv tensor (last axis)."""
    d = model["d_model"]
    kvw = model["n_kv_heads"] * (d // model["n_heads"])
    return {"q": x[..., :d], "k": x[..., d:d + kvw], "v": x[..., d + kvw:]}


def n_params(model: dict) -> int:
    s = shapes(model)
    per_layer = sum(math.prod(s[n]) for n in LAYER)
    outer = sum(math.prod(s[n]) for n in OUTER if n in s)
    return model["n_layers"] * per_layer + outer


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number (the driver's seeds pass
    2**31, which a 32-bit key constructor would fold away)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def _tensor(model: dict, key, name: str, shape, dtype):
    kind = name.rsplit(".", 1)[-1]
    if name in ("embed", "pos"):
        x = jax.random.normal(key, shape, jnp.float32)
    elif kind == "scale":
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif kind == "bias":
        x = 0.1 * jax.random.normal(key, shape, jnp.float32)
    else:   # a matrix (in, out) or its bias (out,): +-1/sqrt(fan_in)
        fan_in = (shape[0] if kind == "w" else
                  model["d_ff"] if name == "ff_out.b" else model["d_model"])
        bound = 1.0 / math.sqrt(fan_in)
        x = jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    return x.astype(dtype)


def _group(model: dict, key, names) -> dict:
    s = shapes(model)
    dtype = jnp.dtype(model["param_dtype"])
    return {n: _tensor(model, jax.random.fold_in(key, i), n, s[n], dtype)
            for i, n in enumerate(names) if n in s}


class Maker:
    """The weights of one model from one seed.  The key and the layer's index
    are traced arguments, so the two small programs compile once for a model
    and are found in the compile cache whatever the seed."""

    def __init__(self, model: dict, seed: int, sharding=None):
        self.model, self.key = model, seed_key(seed)
        self._outer = jax.jit(
            lambda key: _group(model, jax.random.fold_in(key, 1 << 20), OUTER),
            out_shardings=sharding)
        self._layer = jax.jit(
            lambda key, i: _group(model, jax.random.fold_in(key, i), LAYER),
            out_shardings=sharding)

    def outer(self) -> dict:
        return self._outer(self.key)

    def layer(self, i: int) -> dict:
        return self._layer(self.key, i)

    def layers(self) -> list:
        return [self.layer(i) for i in range(self.model["n_layers"])]
