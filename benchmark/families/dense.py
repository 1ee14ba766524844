"""Family ``dense``: one pre-LN decoder block with a two-matrix feed-forward.

Everything the benchmark knows about this kind of block, and nothing about
windows or clocks: (1) the model keys a configuration's ``mapping`` spells,
(2) the tensors and how each is initialised, (3) the adapter to the program's
config, flags and parameter tree, (4) the plain float32 reference of the block,
(5) the counts of operations and bytes.  The harness, the reference's walks and
``reducers/counts.py`` find these by the family's name (``common.load_family``).

It covers the options the benchmark's configurations use: learned positions or
rotary ones, multi-head or grouped-query attention, an ungated tanh-GELU
feed-forward, biased LayerNorm and biased projections, an untied head.

Tensors are named flat: ``embed``, ``pos`` (learned positions only), ``ln_f.scale``,
``ln_f.bias``, ``head.w`` outside the layers and ``ln1.scale`` ... ``ff_out.b``
inside one.  Matrices are stored ``(in, out)``; the fused qkv projection is laid
out ``[q | k | v]`` with heads contiguous.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..harness import weights
from ..reducers import counts

F32 = jnp.float32

# ---- 1. the model keys a configuration's ``mapping`` must spell -------------

MODEL_KEYS = ("vocab_size", "d_model", "n_layers", "n_heads", "n_kv_heads",
              "d_ff", "max_seq_len", "ln_eps", "pos_encoding", "rope_theta",
              "activation", "param_dtype", "compute_dtype")

# ---- 2. tensors: names, shapes, initialisation ------------------------------

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


def outer_shapes(model: dict) -> dict:
    """The outer part's tensors in the order their keys are drawn; one the
    model lacks (``pos`` under rotary positions) keeps its place with None."""
    s = shapes(model)
    return {n: s.get(n) for n in OUTER}


def layer_shapes(model: dict, i: int) -> dict:
    """Layer ``i``'s tensors, likewise; every layer of this family is alike."""
    s = shapes(model)
    return {n: s[n] for n in LAYER}


def init_tensor(model: dict, key, name: str, shape, dtype):
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


def split_qkv(model: dict, x) -> dict:
    """The q, k and v columns of a fused qkv tensor (last axis)."""
    d = model["d_model"]
    kvw = model["n_kv_heads"] * (d // model["n_heads"])
    return {"q": x[..., :d], "k": x[..., d:d + kvw], "v": x[..., d + kvw:]}


def leaves(model: dict, tensors: dict) -> dict:
    """The benchmark's tensors of one layer (or of the outer part), stacked
    over layers or not, as the leaves the comparison names.  The fused qkv
    projection is split into its q, k and v columns: they are three tensors
    of the published model, and the key's bias has no gradient under
    softmax."""
    out = {}
    for n, x in tensors.items():
        base, part = n.rsplit(".", 1) if "." in n else (n, "")
        if base == "qkv":
            out.update({f"{m}.{part}": y
                        for m, y in split_qkv(model, x).items()})
        else:
            out[n] = x
    return out


# ---- 3. the program adapter -------------------------------------------------
# The one place that knows how the program spells such a model: its
# ``TransformerConfig``, the trainer's command-line flags, its parameter tree.

ACTIVATIONS = {"gelu_tanh": "gelu"}      # the program's gelu is the tanh form
_BLOCK = ("ln1", "qkv", "attn_out", "ln2", "ff_in", "ff_out")


def transformer_config(model: dict):
    from neural_networks_parallel_training_with_mpi_tpu.models import (
        TransformerConfig,
    )

    kv = model["n_kv_heads"]
    return TransformerConfig(
        vocab_size=model["vocab_size"], max_seq_len=model["max_seq_len"],
        n_layers=model["n_layers"], d_model=model["d_model"],
        n_heads=model["n_heads"], d_ff=model["d_ff"],
        activation=ACTIVATIONS[model["activation"]],
        pos_encoding=model["pos_encoding"],
        rope_theta=model["rope_theta"] or 10000.0,
        n_kv_heads=None if kv == model["n_heads"] else kv,
        param_dtype=jnp.dtype(model["param_dtype"]),
        compute_dtype=jnp.dtype(model["compute_dtype"]))


def program_model(model: dict):
    """The program's model object, as the server takes it."""
    from neural_networks_parallel_training_with_mpi_tpu.models import (
        Transformer,
    )

    return Transformer(transformer_config(model))


def train_flags(model: dict, job: dict, seed: int, out_dir) -> list:
    """The flags ``cli.main`` would parse for this model and job."""
    if model["ln_eps"] != 1e-5:
        raise ValueError(
            f"configuration {model['config']!r} states ln_eps "
            f"{model['ln_eps']!r}: the program's LayerNorm has eps 1e-5 and "
            "no flag")
    opt = job["optimizer"]
    flags = [
        "--dataset", "lm", "--arch", "transformer", "--loss", "cross_entropy",
        "--vocab_size", str(model["vocab_size"]),
        "--seq_len", str(job["seq_len"]),
        "--n_layers", str(model["n_layers"]),
        "--d_model", str(model["d_model"]),
        "--n_heads", str(model["n_heads"]), "--d_ff", str(model["d_ff"]),
        "--ffn_activation", ACTIVATIONS[model["activation"]],
        "--pos_encoding", model["pos_encoding"],
        "--dtype", model["param_dtype"],
        "--compute_dtype", model["compute_dtype"],
        "--no-full-batch", "--batch_size", str(job["global_batch"]),
        "--no-shuffle", "--optimizer", opt["name"], "--lr", str(opt["lr"]),
        "--weight_decay", str(opt["weight_decay"]),
        "--nepochs", "100000", "--seed", str(seed & 0x7FFFFFFF),
        "--metrics_jsonl", str(out_dir / "train_metrics.jsonl"),
        "--trace_dir", str(out_dir / "train_trace"),
    ]
    if model["n_kv_heads"] != model["n_heads"]:
        flags += ["--n_kv_heads", str(model["n_kv_heads"])]
    return flags + [str(f) for f in job.get("flags", [])]


def _lin(p, name):
    return {"w": p[f"{name}.w"], "b": p[f"{name}.b"]}


def _ln(p, name):
    return {"scale": p[f"{name}.scale"], "bias": p[f"{name}.bias"]}


def to_program_layer(model: dict, p: dict, i: int) -> dict:
    return {n: (_ln if n.startswith("ln") else _lin)(p, n) for n in _BLOCK}


def to_program_outer(model: dict, outer: dict) -> dict:
    tree = {"embed": {"table": outer["embed"]}, "ln_f": _ln(outer, "ln_f"),
            "head": {"w": outer["head.w"]}}
    if "pos" in outer:
        tree["pos"] = {"table": outer["pos"]}
    return tree


def to_program(model: dict, outer: dict, layers: list) -> dict:
    """The program's parameter tree from the benchmark's flat tensors."""
    return {**to_program_outer(model, outer),
            "blocks": [to_program_layer(model, p, i)
                       for i, p in enumerate(layers)]}


def split_program(model: dict, tree: dict):
    """A tree shaped like the program's parameters -> (its outer part, the
    list of its layers)."""
    return ({k: v for k, v in tree.items() if k != "blocks"}, tree["blocks"])


def outer_leaves(model: dict, tree: dict) -> dict:
    out = {"embed": tree["embed"]["table"], "head.w": tree["head"]["w"],
           "ln_f.scale": tree["ln_f"]["scale"],
           "ln_f.bias": tree["ln_f"]["bias"]}
    if "pos" in tree:
        out["pos"] = tree["pos"]["table"]
    return out


def layer_leaves(model: dict, blk: dict) -> dict:
    """One block of the program's tree -> {leaf name: leaf}, by the names
    ``leaves`` gives the reference's."""
    return leaves(model, {
        f"{n}.{part}": blk[n][part] for n in _BLOCK
        for part in (("scale", "bias") if n.startswith("ln") else ("w", "b"))})


# ---- 4. the plain reference: float32 ``jax.numpy`` --------------------------
# No cache, no kernels, no batching tricks, nothing imported from the program.
# Every caller runs it under ``jax.default_matmul_precision("highest")``: on a
# TPU a float32 product is otherwise made in lower precision.  ``quant`` is the
# control's hook and no part of the reference proper: a function applied to
# both operands of every projection, with which the control computes the same
# pass in the nearest precision below the configuration's.


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


def attention_half(model, p, x, quant=None):
    """x + Attn(LN(x)), the first half of the block."""
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
    return x + dense(a, p["attn_out.w"], p["attn_out.b"], quant)


def block(model, p, x, i, quant=None):
    """x + Attn(LN(x)), then x + FFN(LN(x)); ``p`` is one layer's tensors by
    the names of ``LAYER``, already float32.  ``i`` is the layer's index
    (traced: one program serves every layer); this family does not read it."""
    x = attention_half(model, p, x, quant)
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


# ---- 5. counts: operations and bytes from shapes ----------------------------


def matmul_params(model: dict) -> int:
    """Parameters that take part in a matrix product for every token: the
    layers' four projections and the head (not the embedding tables, which
    are looked up, nor norms and biases)."""
    s = shapes(model)
    per_layer = sum(s[n][0] * s[n][1]
                    for n in ("qkv.w", "attn_out.w", "ff_in.w", "ff_out.w"))
    return model["n_layers"] * per_layer + s["head.w"][0] * s["head.w"][1]


def attention_flops(model: dict, context):
    """One token's scores and values over ``context`` keys (a number or an
    array of them), all layers."""
    return 4.0 * model["n_layers"] * model["d_model"] * context


def decode_weight_bytes(model: dict, obs=None) -> int:
    """Bytes a decode tick has to read of the weights: everything but the
    embedding tables (of which it reads one row a stream).  Every tick reads
    every matrix here, so nothing the harness observed (``obs``) enters."""
    s = shapes(model)
    tables = s["embed"][0] * s["embed"][1] + (
        s["pos"][0] * s["pos"][1] if "pos" in s else 0)
    return ((weights.n_params(model) - tables)
            * counts.dtype_bytes(model["param_dtype"]))


def cache_bytes_per_token(model: dict) -> int:
    """K and V of every KV head in every layer."""
    hd = model["d_model"] // model["n_heads"]
    return (2 * model["n_layers"] * model["n_kv_heads"] * hd
            * counts.dtype_bytes(model["compute_dtype"]))
