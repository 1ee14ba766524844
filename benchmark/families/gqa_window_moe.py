"""Family ``gqa_window_moe``: a pre-norm decoder whose layers are not all
alike, as one chip's share of an expert-parallel deployment.

``x + Attn_l(n1(x))``, then ``x + FFN_l(n2(x))``, where layer ``l``'s kinds
come from the configuration's ``layer_types`` and ``mlp_layer_types``:

* ``n`` is RMSNorm (a learned scale, no mean, no bias), statistics in float32;
* attention is grouped-query with a head width of its own (the query
  projection is ``n_heads * head_dim`` wide, not ``d_model``): ``q = h W_q``,
  ``k = h W_k``, ``v = h W_v``, no biases; ``q`` and ``k`` are RMS-normed over
  the lanes of each head (one scale vector of ``head_dim`` for all heads);
  query head ``n`` reads key/value head ``n // (n_heads / n_kv_heads)``;
  scores ``q . k / sqrt(head_dim)``, softmax in float32 over the keys seen;
* a ``sliding_attention`` layer rotates ``q`` and ``k`` by position (pairs
  ``(i, i + head_dim / 2)``, angle ``pos * theta^(-i / (head_dim / 2))``) and
  its query ``i`` sees key ``j`` iff ``0 <= i - j < sliding_window``; a
  ``full_attention`` layer does NOT rotate and sees every ``j <= i``;
* a ``dense`` layer's feed-forward is gated, ``(silu(y W_g) * (y W_u)) W_d``
  at width ``dense_ff``; a ``sparse`` layer's is ``E_shared(y) + sum over the
  chosen e held here of w_e E_e(y)`` with gated experts of width ``expert_ff``;
* the router scores ``s = sigmoid(y W_r)`` in float32 over ALL
  ``experts_total`` experts; the ``top_k`` chosen are the largest of ``s + b``
  (``b`` the stored correction bias), their weights ``s_e / (sum of the chosen
  s + 1e-20) * routed_scale``: the bias is in the choice, not in the weight;
* **the share**: this chip holds the contiguous experts ``[experts_first,
  experts_first + experts_held)`` of every sparse layer and ``vocab_size`` rows
  of the vocabulary.  ``layer_shapes`` gives exactly those to the program and
  to the reference alike; what the absent experts would have added is left out
  by both, and that partial result goes on to the next layer.

Everything the benchmark knows about this kind of block, in the five parts
``benchmark/README.md`` lists.  The reference imports nothing from the program.

Tensors are named flat; matrices are stored ``(in, out)``, expert stacks
``(held, in, out)``; ``qkv.w`` is laid out ``[q | k | v]`` with heads
contiguous.  A tensor a layer's kind lacks keeps its place in ``LAYER`` with
shape None (``harness/weights.py`` makes nothing for it).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..harness import weights
from ..reducers import counts

F32 = jnp.float32

# ---- 1. the model keys a configuration's ``mapping`` must spell -------------

MODEL_KEYS = ("vocab_size", "d_model", "n_layers", "n_heads", "n_kv_heads",
              "head_dim", "layer_types", "mlp_layer_types", "sliding_window",
              "dense_ff", "expert_ff", "shared_experts", "experts_total",
              "experts_first", "experts_held", "top_k", "routed_scale",
              "router_bias_std", "max_seq_len", "rms_eps", "rope_theta",
              "param_dtype", "compute_dtype")

WINDOW, FULL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"


def attention_kinds(model: dict) -> list:
    """Each kept layer's attention kind: the first ``n_layers`` of the
    published list (the cut keeps the leading layers)."""
    kinds = list(model["layer_types"][:model["n_layers"]])
    if set(kinds) - {WINDOW, FULL} or len(kinds) != model["n_layers"]:
        raise ValueError(f"configuration {model['config']!r}: layer_types "
                         f"{kinds} for {model['n_layers']} layers")
    return kinds


def ffn_kinds(model: dict) -> list:
    kinds = list(model["mlp_layer_types"][:model["n_layers"]])
    if set(kinds) - {DENSE, SPARSE} or len(kinds) != model["n_layers"]:
        raise ValueError(f"configuration {model['config']!r}: "
                         f"mlp_layer_types {kinds}")
    return kinds


# ---- 2. tensors: names, shapes, initialisation ------------------------------

OUTER = ("embed", "norm_f.scale", "head.w")
ATTENTION = ("qkv.w", "attn_out.w")
FFN = ("ffn.w_gate", "ffn.w_up", "ffn.w_down")
EXPERTS = ("experts.w_gate", "experts.w_up", "experts.w_down")
SHARED = ("shared.w_gate", "shared.w_up", "shared.w_down")
ROUTER = ("router.w", "router.bias")
LAYER = ("norm1.scale", "qkv.w", "q_norm.scale", "k_norm.scale",
         "attn_out.w", "norm2.scale", *FFN, *ROUTER, *EXPERTS, *SHARED)


def shapes(model: dict) -> dict:
    d, v, h, kv, hd = (model["d_model"], model["vocab_size"],
                       model["n_heads"], model["n_kv_heads"],
                       model["head_dim"])
    f, ff, held = model["expert_ff"], model["dense_ff"], model["experts_held"]
    fs = f * model["shared_experts"]
    return {"embed": (v, d), "norm_f.scale": (d,), "head.w": (d, v),
            "norm1.scale": (d,), "qkv.w": (d, (h + 2 * kv) * hd),
            "q_norm.scale": (hd,), "k_norm.scale": (hd,),
            "attn_out.w": (h * hd, d), "norm2.scale": (d,),
            "ffn.w_gate": (d, ff), "ffn.w_up": (d, ff),
            "ffn.w_down": (ff, d),
            "router.w": (d, model["experts_total"]),
            "router.bias": (model["experts_total"],),
            "experts.w_gate": (held, d, f), "experts.w_up": (held, d, f),
            "experts.w_down": (held, f, d),
            "shared.w_gate": (d, fs), "shared.w_up": (d, fs),
            "shared.w_down": (fs, d)}


def outer_shapes(model: dict) -> dict:
    s = shapes(model)
    return {n: s[n] for n in OUTER}


def layer_shapes(model: dict, i: int) -> dict:
    """Layer ``i``'s tensors in ``LAYER``'s order; what its feed-forward's
    kind lacks keeps its place with None.  (The attention's kind changes
    what a layer does, not what it holds.)"""
    s = shapes(model)
    lacks = (*ROUTER, *EXPERTS, *SHARED) if ffn_kinds(model)[i] == DENSE \
        else FFN
    return {n: None if n in lacks else s[n] for n in LAYER}


def init_tensor(model: dict, key, name: str, shape, dtype):
    if name == "embed":
        x = jax.random.normal(key, shape, F32)
    elif name.endswith(".scale"):
        x = 1.0 + 0.1 * jax.random.normal(key, shape, F32)
    elif name == "router.bias":
        # small against the spacing of the scores near the k-th largest:
        # it moves some choices and not most (the configuration says what
        # share)
        x = model["router_bias_std"] * jax.random.normal(key, shape, F32)
    else:       # a matrix (in, out) or a stack of them: +-1/sqrt(fan_in)
        bound = 1.0 / math.sqrt(shape[-2])
        x = jax.random.uniform(key, shape, F32, -bound, bound)
    return x.astype(dtype)


def leaves(model: dict, tensors: dict) -> dict:
    """The leaves the comparison names: the tensors themselves."""
    return dict(tensors)


# ---- 3. the program adapter -------------------------------------------------

_NORMS = {"norm1": "ln1", "norm2": "ln2", "q_norm": "q_norm",
          "k_norm": "k_norm"}
_LINEAR = {"qkv": "qkv", "attn_out": "attn_out"}
_FFN = {"w_gate": "ff_gate", "w_up": "ff_in", "w_down": "ff_out"}
_EXPERT = {"w_gate": "w_gate", "w_up": "w_in", "w_down": "w_out"}


def attention_pattern(model: dict) -> str:
    """The kept layers' kinds as the program's pattern string (it repeats
    a pattern over the depth; the whole list is one period)."""
    return "".join("L" if k == WINDOW else "G" for k in attention_kinds(model))


def first_dense(model: dict) -> int:
    kinds = ffn_kinds(model)
    n = kinds.index(SPARSE) if SPARSE in kinds else len(kinds)
    if DENSE in kinds[n:]:
        raise ValueError(f"configuration {model['config']!r}: the program "
                         "takes dense layers before the sparse ones only, "
                         f"got {kinds}")
    return n


def transformer_config(model: dict):
    try:
        from neural_networks_parallel_training_with_mpi_tpu.models import (
            TransformerConfig,
        )

        return TransformerConfig(
            vocab_size=model["vocab_size"], max_seq_len=model["max_seq_len"],
            n_layers=model["n_layers"], d_model=model["d_model"],
            n_heads=model["n_heads"], n_kv_heads=model["n_kv_heads"],
            head_width=model["head_dim"], d_ff=model["expert_ff"],
            activation="swiglu", pos_encoding="rope",
            rope_theta=float(model["rope_theta"]), norm="rmsnorm",
            norm_eps=model["rms_eps"], use_bias=False, qk_norm=True,
            attention_pattern=attention_pattern(model),
            sliding_window=model["sliding_window"], rope_global=False,
            moe_experts=model["experts_total"], moe_top_k=model["top_k"],
            moe_dropless=True,
            moe_experts_held=(model["experts_first"], model["experts_held"]),
            moe_shared_ff=model["expert_ff"] * model["shared_experts"],
            moe_score="sigmoid",
            moe_routed_scale=float(model["routed_scale"]),
            moe_first_dense=first_dense(model), dense_ff=model["dense_ff"],
            param_dtype=jnp.dtype(model["param_dtype"]),
            compute_dtype=jnp.dtype(model["compute_dtype"]))
    except (ImportError, TypeError) as e:
        # a program from before layers of several kinds: say what is
        # missing and stop, before any weight is made
        raise SystemExit(
            f"benchmark: the program in this checkout cannot build "
            f"configuration {model['config']!r} (family gqa_window_moe): it "
            f"lacks a head width of its own, the per-head norm of q and k, "
            f"window and full attention layers in one model, a leading "
            f"dense layer or the sigmoid router ({type(e).__name__}: {e})"
        ) from None


def program_model(model: dict):
    from neural_networks_parallel_training_with_mpi_tpu.models import (
        Transformer,
    )

    return Transformer(transformer_config(model))


def train_flags(model: dict, job: dict, seed: int, out_dir) -> list:
    """The flags ``cli.main`` would parse for this model and job."""
    opt = job["optimizer"]
    return [
        "--dataset", "lm", "--arch", "transformer", "--loss", "cross_entropy",
        "--vocab_size", str(model["vocab_size"]),
        "--seq_len", str(job["seq_len"]),
        "--n_layers", str(model["n_layers"]),
        "--d_model", str(model["d_model"]),
        "--n_heads", str(model["n_heads"]),
        "--n_kv_heads", str(model["n_kv_heads"]),
        "--head_width", str(model["head_dim"]),
        "--d_ff", str(model["expert_ff"]), "--ffn_activation", "swiglu",
        "--pos_encoding", "rope", "--rope_theta", str(model["rope_theta"]),
        "--norm", "rmsnorm", "--norm_eps", str(model["rms_eps"]), "--no-bias",
        "--qk-norm", "--attention_pattern", attention_pattern(model),
        "--sliding_window", str(model["sliding_window"]), "--no-rope-global",
        "--moe_experts", str(model["experts_total"]),
        "--moe_top_k", str(model["top_k"]), "--moe-dropless",
        "--moe_experts_held",
        f"{model['experts_first']},{model['experts_held']}",
        "--moe_shared_ff", str(model["expert_ff"] * model["shared_experts"]),
        "--moe_score", "sigmoid",
        "--moe_routed_scale", str(model["routed_scale"]),
        "--moe_first_dense", str(first_dense(model)),
        "--dense_ff", str(model["dense_ff"]),
        "--dtype", model["param_dtype"],
        "--compute_dtype", model["compute_dtype"],
        "--no-full-batch", "--batch_size", str(job["global_batch"]),
        "--no-shuffle", "--optimizer", opt["name"], "--lr", str(opt["lr"]),
        "--weight_decay", str(opt["weight_decay"]),
        "--nepochs", "100000", "--seed", str(seed & 0x7FFFFFFF),
        "--metrics_jsonl", str(out_dir / "train_metrics.jsonl"),
        "--trace_dir", str(out_dir / "train_trace"),
    ] + [str(f) for f in job.get("flags", [])]


def to_program_layer(model: dict, p: dict, i: int) -> dict:
    blk = {prog: {"scale": p[f"{mine}.scale"]}
           for mine, prog in _NORMS.items()}
    blk.update({prog: {"w": p[f"{mine}.w"]} for mine, prog in _LINEAR.items()})
    if "ffn.w_gate" in p:
        blk.update({prog: {"w": p[f"ffn.{mine}"]}
                    for mine, prog in _FFN.items()})
    else:
        blk["moe"] = {"gate": {"w": p["router.w"], "bias": p["router.bias"]},
                      "experts": {prog: p[f"experts.{mine}"]
                                  for mine, prog in _EXPERT.items()},
                      "shared": {prog: p[f"shared.{mine}"]
                                 for mine, prog in _EXPERT.items()}}
    return blk


def to_program_outer(model: dict, outer: dict) -> dict:
    return {"embed": {"table": outer["embed"]},
            "ln_f": {"scale": outer["norm_f.scale"]},
            "head": {"w": outer["head.w"]}}


def to_program(model: dict, outer: dict, layers: list) -> dict:
    return {**to_program_outer(model, outer),
            "blocks": [to_program_layer(model, p, i)
                       for i, p in enumerate(layers)]}


def split_program(model: dict, tree: dict):
    return ({k: v for k, v in tree.items() if k != "blocks"}, tree["blocks"])


def outer_leaves(model: dict, tree: dict) -> dict:
    return {"embed": tree["embed"]["table"],
            "norm_f.scale": tree["ln_f"]["scale"], "head.w": tree["head"]["w"]}


def layer_leaves(model: dict, blk: dict) -> dict:
    out = {f"{mine}.scale": blk[prog]["scale"]
           for mine, prog in _NORMS.items()}
    out.update({f"{mine}.w": blk[prog]["w"] for mine, prog in _LINEAR.items()})
    if "moe" not in blk:
        out.update({f"ffn.{mine}": blk[prog]["w"]
                    for mine, prog in _FFN.items()})
        return out
    out["router.w"] = blk["moe"]["gate"]["w"]
    out["router.bias"] = blk["moe"]["gate"]["bias"]
    for mine, prog in _EXPERT.items():
        out[f"experts.{mine}"] = blk["moe"]["experts"][prog]
        out[f"shared.{mine}"] = blk["moe"]["shared"][prog]
    return out


# ---- 4. the plain reference: float32 ``jax.numpy`` --------------------------
# No cache, no kernels, no batching, nothing imported from the program.
# Every caller runs it under ``jax.default_matmul_precision("highest")``.
# ``quant`` is the control's hook (``reference/control.py``), applied to both
# operands of every projection, the router's and the experts' included.

QUERY_BLOCK = 256       # attention is computed this many queries at a time


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rotate(model: dict, x, positions):
    """Rotate ``x`` (..., T, H, D) by ``positions`` (T,): pairs ``(i, i +
    D/2)``, angle ``pos * theta^(-i / (D/2))``, every lane."""
    half = x.shape[-1] // 2
    freqs = float(model["rope_theta"]) ** (
        -jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def mm(x, w, quant=None):
    if quant is not None:
        x, w = quant(x), quant(w)
    return x @ w


def attention_half(model, p, x, i, quant=None):
    """``x + Attn_i(n1(x))`` over (B, T, d), positions 0..T-1,
    ``QUERY_BLOCK`` queries at a time.  ``i`` may be traced (one program
    serves every layer of a shape): the layer's kind is looked up in a
    constant table, and the window and the rotation are selected by it."""
    b, t, d = x.shape
    h, kv, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    g, eps, win = h // kv, model["rms_eps"], model["sliding_window"]
    windowed = jnp.asarray(
        [k == WINDOW for k in attention_kinds(model)])[i]
    pos = jnp.arange(t)
    y = rms_norm(x, p["norm1.scale"], eps)
    qkv = mm(y, p["qkv.w"], quant)
    q = qkv[..., :h * hd].reshape(b, t, h, hd)
    k = qkv[..., h * hd:(h + kv) * hd].reshape(b, t, kv, hd)
    v = qkv[..., (h + kv) * hd:].reshape(b, t, kv, hd)
    q = rms_norm(q, p["q_norm.scale"], eps)
    k = rms_norm(k, p["k_norm.scale"], eps)
    # a window layer carries positions, a full layer none
    q = jnp.where(windowed, rotate(model, q, pos), q)
    k = jnp.where(windowed, rotate(model, k, pos), k)
    q = q.reshape(b, t, kv, g, hd)      # query head n reads kv head n // g
    scale = hd ** -0.5

    def some_queries(args):
        qb, pb = args                           # (B, Q, KV, G, D), (Q,)
        s = jnp.einsum("bqcgd,bkcd->bcgqk", qb, k) * scale
        back = pb[:, None] - pos[None, :]       # i - j
        seen = (back >= 0) & (~windowed | (back < win))
        s = jnp.where(seen[None, None, None], s, -jnp.inf)
        return jnp.einsum("bcgqk,bkcd->bqcgd", jax.nn.softmax(s, -1), v)

    blk = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    n = t // blk
    o = jax.lax.map(some_queries, (
        q.reshape(b, n, blk, kv, g, hd).swapaxes(0, 1), pos.reshape(n, blk)))
    o = o.swapaxes(0, 1).reshape(b, t, h * hd)
    return x + mm(o, p["attn_out.w"], quant)


def gated(y, w_gate, w_up, w_down, quant=None):
    return mm(jax.nn.silu(mm(y, w_gate, quant)) * mm(y, w_up, quant),
              w_down, quant)


def router_choice(model, p, y, quant=None):
    """((B, T, k) weights, (B, T, k) expert ids): sigmoid scores in float32
    over ALL experts, the ``top_k`` largest of ``score + bias`` chosen,
    their SCORES divided by their sum (+ 1e-20) and times ``routed_scale``."""
    s = jax.nn.sigmoid(mm(y, p["router.w"], quant))
    _, top_i = jax.lax.top_k(s + p["router.bias"], model["top_k"])
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    w = top_s / (top_s.sum(-1, keepdims=True) + 1e-20) * model["routed_scale"]
    return w, top_i


def combine_weights(model, p, y, quant=None):
    """(B, T, held): each token's weight on each expert held here; 0 where
    the expert is not among the token's choices."""
    w, top_i = router_choice(model, p, y, quant)
    held = model["experts_first"] + jnp.arange(model["experts_held"])
    return (w[..., None] * (top_i[..., None] == held)).sum(-2)


def routed(model, p, y, quant=None):
    """``sum over the chosen e held here of w_e E_e(y)``: every held expert
    over every token, one expert at a time, weighted by 0 where it was not
    chosen (plain; the expert could be computed over its own tokens only)."""
    w = combine_weights(model, p, y, quant)

    def one(acc, e):
        wg, wu, wd, we = e
        return acc + we[..., None] * gated(y, wg, wu, wd, quant), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        p["experts.w_gate"], p["experts.w_up"], p["experts.w_down"],
        jnp.moveaxis(w, -1, 0)))
    return out


def block(model, p, x, i, quant=None):
    """Layer ``i`` (traced or not); ``p`` holds its tensors by the names of
    ``LAYER``, already float32.  The feed-forward's kind is read from what
    ``p`` holds, the attention's from ``i``."""
    x = attention_half(model, p, x, i, quant)
    y = rms_norm(x, p["norm2.scale"], model["rms_eps"])
    if "ffn.w_gate" in p:
        return x + gated(y, p["ffn.w_gate"], p["ffn.w_up"], p["ffn.w_down"],
                         quant)
    return (x + gated(y, p["shared.w_gate"], p["shared.w_up"],
                      p["shared.w_down"], quant)
            + routed(model, p, y, quant))


def embed(model, outer, ids):
    return outer["embed"].astype(F32)[ids]


def head_logits(model, outer, x, quant=None):
    y = rms_norm(x, outer["norm_f.scale"].astype(F32), model["rms_eps"])
    return mm(y, outer["head.w"].astype(F32), quant)


# ---- 5. counts: operations and bytes from shapes ----------------------------


def _size(model: dict, names) -> int:
    s = shapes(model)
    return sum(math.prod(s[n]) for n in names)


def expert_params(model: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * model["d_model"] * model["expert_ff"]


def n_sparse(model: dict) -> int:
    return ffn_kinds(model).count(SPARSE)


def n_window(model: dict) -> int:
    return attention_kinds(model).count(WINDOW)


def matmul_params(model: dict) -> float:
    """Parameters a token meets in a matrix product, honouring the cut: the
    attention's projections in every layer; a dense layer's feed-forward at
    its own width; in a sparse layer the shared expert, the router, and of
    its ``top_k`` routed experts the share that is held here IN EXPECTATION
    (``top_k * held / total`` experts a token: uniform routing is assumed,
    the counters say what ran); and the slice of the head held here."""
    sparse = n_sparse(model)
    per_sparse = (_size(model, SHARED) + _size(model, ("router.w",))
                  + model["top_k"] * model["experts_held"]
                  / model["experts_total"] * expert_params(model))
    return (model["n_layers"] * _size(model, ATTENTION)
            + (model["n_layers"] - sparse) * _size(model, FFN)
            + sparse * per_sparse + _size(model, ("head.w",)))


def attention_flops(model: dict, context):
    """One token's scores and values over ``context`` keys (a number or an
    array of them), all layers: a full layer counts every key, a window
    layer ``min(context, sliding_window)``."""
    per_key = 2.0 * model["n_heads"] * 2 * model["head_dim"]
    window = n_window(model)
    return per_key * ((model["n_layers"] - window) * context
                      + window * np.minimum(context,
                                            model["sliding_window"]))


def kv_row_bytes(model: dict) -> int:
    """K and V of one token in ONE layer."""
    return (2 * model["n_kv_heads"] * model["head_dim"]
            * counts.dtype_bytes(model["compute_dtype"]))


def cache_bytes_per_token(model: dict) -> int:
    """The FULL layers' rows alone.  ``counts.decode_hbm_share`` multiplies
    this by whole stream lengths, and a window layer reads at most its
    window of a stream however long it is: leaving the window layers' few
    pages a stream out makes the share a lower bound
    (``paged_attention_roofline.serve`` counts both kinds from the
    program's counters)."""
    return (model["n_layers"] - n_window(model)) * kv_row_bytes(model)


def expert_counters(obs) -> dict | None:
    """What the window's ticks did at the experts (and, since the program
    stamps them together, at the attention's two kinds of layer), from the
    cumulative counters on the scheduler's ``retire`` spans
    (``obs["spans"]``): last reading minus first.  None where no span carries
    them, or under two readings."""
    seen = [a for n, _t, _d, a in (obs or {}).get("spans", ())
            if n == "retire" and "decode_ticks_counted" in a]
    if len(seen) < 2:
        return None
    return {k: seen[-1][k] - seen[0][k] for k in seen[0]
            if isinstance(seen[0][k], int) and k != "tick"}


def decode_weight_bytes(model: dict, obs=None) -> float:
    """Bytes a decode tick has to read of the weights: everything outside
    the routed experts and the embedding table, plus the held experts that
    the window's ticks reached (``experts_reached`` a tick, summed over the
    sparse layers, from the counters; every held expert where there are
    none)."""
    width = counts.dtype_bytes(model["param_dtype"])
    fixed = (weights.n_params(model) - _size(model, ("embed",))
             - n_sparse(model) * _size(model, EXPERTS))
    seen = expert_counters(obs)
    if seen and seen["decode_ticks_counted"]:
        reached = seen["experts_reached"] / seen["decode_ticks_counted"]
    else:
        reached = n_sparse(model) * model["experts_held"]
    return (fixed + reached * expert_params(model)) * width
