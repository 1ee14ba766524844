"""Family ``mla_moe``: a pre-norm decoder block with latent attention and a
routed feed-forward, as one chip's share of an expert-parallel deployment.

``x + LatentAttn(n1(x))``, then ``x + E_shared(y) + sum over the chosen e held
here of w_e E_e(y)`` at ``y = n2(x)``:

* ``n`` is RMSNorm (a learned scale, no mean, no bias), statistics in float32;
* latent attention: ``c_q = n_q(h W_qa)``, ``q = c_q W_qb`` -> heads x
  ``[q_nope | q_rope]``; ``h W_kva -> [c | k_r]``, ``c_kv = n_kv(c)``,
  ``k_rope = R_p(k_r)`` (one head, shared by all), ``c_kv W_kvb`` -> heads x
  ``[k_nope | v]``; ``R_p`` rotates adjacent pairs by YaRN's blended
  frequencies; queries are scaled by position; scores
  ``(q_nope . k_nope + R_p(q_rope) . k_rope) * qk_head_dim^-0.5 *
  m(mscale_all_dim)^2``, causal softmax, no biases anywhere;
* the router is a float32 softmax over ALL ``experts_total`` experts, the
  ``top_k`` largest renormalised to sum 1, times ``routed_scale``; an expert
  is gated, ``E(y) = (silu(y W_g) * (y W_u)) W_d``;
* **the share**: this chip holds the contiguous experts ``[experts_first,
  experts_first + experts_held)`` of every layer and ``vocab_size`` rows of
  the vocabulary.  ``layer_shapes`` gives exactly those experts to the program
  and to the reference alike; what the absent experts would have added is left
  out by both, and that partial result goes on to the next layer.

Everything the benchmark knows about this kind of block, in the five parts
``benchmark/README.md`` lists.  The reference imports nothing from the program.

Tensors are named flat; matrices are stored ``(in, out)``, expert stacks
``(held, in, out)``; ``q_b.w`` is laid out heads x ``[nope | rope]`` and
``kv_b.w`` heads x ``[k_nope | v]``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..harness import weights
from ..reducers import counts

F32 = jnp.float32

# ---- 1. the model keys a configuration's ``mapping`` must spell -------------

MODEL_KEYS = ("vocab_size", "d_model", "n_layers", "n_heads", "q_lora_rank",
              "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
              "v_head_dim", "expert_ff", "shared_experts", "experts_total",
              "experts_first", "experts_held", "top_k", "routed_scale",
              "max_seq_len", "rms_eps", "rope_theta", "rope", "param_dtype",
              "compute_dtype")

# ---- 2. tensors: names, shapes, initialisation ------------------------------

OUTER = ("embed", "norm_f.scale", "head.w")
LAYER = ("norm1.scale", "q_a.w", "q_norm.scale", "q_b.w", "kv_a.w",
         "kv_norm.scale", "kv_b.w", "attn_out.w", "norm2.scale", "router.w",
         "experts.w_gate", "experts.w_up", "experts.w_down",
         "shared.w_gate", "shared.w_up", "shared.w_down")
ATTENTION = ("q_a.w", "q_b.w", "kv_a.w", "kv_b.w", "attn_out.w")
EXPERTS = ("experts.w_gate", "experts.w_up", "experts.w_down")
SHARED = ("shared.w_gate", "shared.w_up", "shared.w_down")


def shapes(model: dict) -> dict:
    d, v, h = model["d_model"], model["vocab_size"], model["n_heads"]
    qr, kr = model["q_lora_rank"], model["kv_lora_rank"]
    nope, rope, vd = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                      model["v_head_dim"])
    f, held = model["expert_ff"], model["experts_held"]
    fs = f * model["shared_experts"]
    return {"embed": (v, d), "norm_f.scale": (d,), "head.w": (d, v),
            "norm1.scale": (d,), "q_a.w": (d, qr), "q_norm.scale": (qr,),
            "q_b.w": (qr, h * (nope + rope)), "kv_a.w": (d, kr + rope),
            "kv_norm.scale": (kr,), "kv_b.w": (kr, h * (nope + vd)),
            "attn_out.w": (h * vd, d), "norm2.scale": (d,),
            "router.w": (d, model["experts_total"]),
            "experts.w_gate": (held, d, f), "experts.w_up": (held, d, f),
            "experts.w_down": (held, f, d),
            "shared.w_gate": (d, fs), "shared.w_up": (d, fs),
            "shared.w_down": (fs, d)}


def outer_shapes(model: dict) -> dict:
    s = shapes(model)
    return {n: s[n] for n in OUTER}


def layer_shapes(model: dict, i: int) -> dict:
    """Layer ``i``'s tensors; every layer is an expert layer
    (``first_k_dense_replace`` 0) and holds the same range of experts."""
    s = shapes(model)
    return {n: s[n] for n in LAYER}


def init_tensor(model: dict, key, name: str, shape, dtype):
    if name == "embed":
        x = jax.random.normal(key, shape, F32)
    elif name.endswith(".scale"):
        x = 1.0 + 0.1 * jax.random.normal(key, shape, F32)
    else:       # a matrix (in, out) or a stack of them: +-1/sqrt(fan_in)
        bound = 1.0 / math.sqrt(shape[-2])
        x = jax.random.uniform(key, shape, F32, -bound, bound)
    return x.astype(dtype)


def leaves(model: dict, tensors: dict) -> dict:
    """The leaves the comparison names: the tensors themselves."""
    return dict(tensors)


# ---- 3. the program adapter -------------------------------------------------

_NORMS = {"norm1": "ln1", "norm2": "ln2"}
_ATTN = {"q_a": "q_a", "q_norm": "q_norm", "q_b": "q_b", "kv_a": "kv_a",
         "kv_norm": "kv_norm", "kv_b": "kv_b", "attn_out": "out"}
_EXPERT = {"w_gate": "w_gate", "w_up": "w_in", "w_down": "w_out"}


def rope_scaling_numbers(model: dict) -> tuple:
    """The seven numbers the program's ``RopeScaling`` takes, from the
    published ``rope_parameters`` group."""
    r = model["rope"]
    return (float(r["factor"]), int(r["original_max_position_embeddings"]),
            float(r["beta_fast"]), float(r["beta_slow"]), float(r["mscale"]),
            float(r["mscale_all_dim"]), float(r["llama_4_scaling_beta"]))


def transformer_config(model: dict):
    try:
        from neural_networks_parallel_training_with_mpi_tpu.models import (
            TransformerConfig,
        )
        from neural_networks_parallel_training_with_mpi_tpu.ops.rope import (
            RopeScaling,
        )

        return TransformerConfig(
            vocab_size=model["vocab_size"], max_seq_len=model["max_seq_len"],
            n_layers=model["n_layers"], d_model=model["d_model"],
            n_heads=model["n_heads"], d_ff=model["expert_ff"],
            pos_encoding="rope", rope_theta=float(model["rope_theta"]),
            norm="rmsnorm", norm_eps=model["rms_eps"], use_bias=False,
            attention_kind="mla", q_lora_rank=model["q_lora_rank"],
            kv_lora_rank=model["kv_lora_rank"],
            qk_nope_head_dim=model["qk_nope_head_dim"],
            qk_rope_head_dim=model["qk_rope_head_dim"],
            v_head_dim=model["v_head_dim"],
            rope_scaling=RopeScaling(*rope_scaling_numbers(model)),
            moe_experts=model["experts_total"], moe_top_k=model["top_k"],
            moe_dropless=True,
            moe_experts_held=(model["experts_first"], model["experts_held"]),
            moe_shared_ff=model["expert_ff"] * model["shared_experts"],
            param_dtype=jnp.dtype(model["param_dtype"]),
            compute_dtype=jnp.dtype(model["compute_dtype"]))
    except (ImportError, TypeError) as e:
        # a program from before latent attention and routing without drops:
        # say what is missing and stop, before any weight is made
        raise SystemExit(
            f"benchmark: the program in this checkout cannot build "
            f"configuration {model['config']!r} (family mla_moe): it lacks "
            f"latent attention (attention_kind='mla'), RMSNorm, the "
            f"long-context rotary or routing without drops over held "
            f"experts ({type(e).__name__}: {e})") from None


def program_model(model: dict):
    from neural_networks_parallel_training_with_mpi_tpu.models import (
        Transformer,
    )

    if model["routed_scale"] != 1:
        raise ValueError(
            f"configuration {model['config']!r} states routed_scale "
            f"{model['routed_scale']!r}: the program's layer has no field "
            "for it and applies 1")
    return Transformer(transformer_config(model))


def train_flags(model: dict, job: dict, seed: int, out_dir) -> list:
    """The flags ``cli.main`` would parse for this model and job."""
    opt = job["optimizer"]
    flags = [
        "--dataset", "lm", "--arch", "transformer", "--loss", "cross_entropy",
        "--vocab_size", str(model["vocab_size"]),
        "--seq_len", str(job["seq_len"]),
        "--n_layers", str(model["n_layers"]),
        "--d_model", str(model["d_model"]),
        "--n_heads", str(model["n_heads"]),
        "--d_ff", str(model["expert_ff"]),
        "--pos_encoding", "rope", "--rope_theta", str(model["rope_theta"]),
        "--norm", "rmsnorm", "--norm_eps", str(model["rms_eps"]), "--no-bias",
        "--attention_kind", "mla",
        "--rope_scaling", ",".join(str(x) for x in
                                   rope_scaling_numbers(model)),
        "--moe_experts", str(model["experts_total"]),
        "--moe_top_k", str(model["top_k"]), "--moe-dropless",
        "--moe_experts_held",
        f"{model['experts_first']},{model['experts_held']}",
        "--moe_shared_ff", str(model["expert_ff"] * model["shared_experts"]),
        "--dtype", model["param_dtype"],
        "--compute_dtype", model["compute_dtype"],
        "--no-full-batch", "--batch_size", str(job["global_batch"]),
        "--no-shuffle", "--optimizer", opt["name"], "--lr", str(opt["lr"]),
        "--weight_decay", str(opt["weight_decay"]),
        "--nepochs", "100000", "--seed", str(seed & 0x7FFFFFFF),
        "--metrics_jsonl", str(out_dir / "train_metrics.jsonl"),
        "--trace_dir", str(out_dir / "train_trace"),
    ]
    for name in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                 "qk_rope_head_dim", "v_head_dim"):
        flags += [f"--{name}", str(model[name])]
    return flags + [str(f) for f in job.get("flags", [])]


def to_program_layer(model: dict, p: dict, i: int) -> dict:
    blk = {prog: {"scale": p[f"{mine}.scale"]}
           for mine, prog in _NORMS.items()}
    blk["attn"] = {prog: ({"scale": p[f"{mine}.scale"]}
                          if mine.endswith("norm") else {"w": p[f"{mine}.w"]})
                   for mine, prog in _ATTN.items()}
    blk["moe"] = {"gate": {"w": p["router.w"]},
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
    for mine, prog in _ATTN.items():
        part = "scale" if mine.endswith("norm") else "w"
        out[f"{mine}.{part}"] = blk["attn"][prog][part]
    out["router.w"] = blk["moe"]["gate"]["w"]
    for mine, prog in _EXPERT.items():
        out[f"experts.{mine}"] = blk["moe"]["experts"][prog]
        out[f"shared.{mine}"] = blk["moe"]["shared"][prog]
    return out


# ---- 4. the plain reference: float32 ``jax.numpy`` --------------------------
# No cache, no kernels, no absorbed form, nothing imported from the program.
# Every caller runs it under ``jax.default_matmul_precision("highest")``.
# ``quant`` is the control's hook (``reference/control.py``), applied to both
# operands of every projection, the router's and the experts' included.

QUERY_BLOCK = 256       # attention is computed this many queries at a time


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def yarn_m(s: float, factor: float) -> float:
    """``m(s) = 0.1 s ln(factor) + 1``."""
    return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_frequencies(model: dict):
    """(rope_dim / 2,) frequencies: ``theta^(-2i/dim)`` where a dimension
    turns at least ``beta_fast`` times within the original context, that
    divided by ``factor`` where it turns at most ``beta_slow`` times, and a
    linear ramp between the two dimensions (YaRN's ``find_correction_range``
    and ``linear_ramp_mask``)."""
    r, dim, theta = model["rope"], model["qk_rope_head_dim"], \
        float(model["rope_theta"])
    half = dim // 2
    base = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / dim)
    if r["factor"] <= 1:
        return base
    orig = r["original_max_position_embeddings"]

    def dim_of(turns):      # the dimension that turns ``turns`` times
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(r["beta_fast"])), 0)
    high = min(math.ceil(dim_of(r["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=F32) - low) / (high - low), 0, 1)
    return base * (1.0 - ramp) + base / r["factor"] * ramp


def rotate_pairs(model: dict, x, positions):
    """Rotate adjacent pairs ``(x_2i, x_2i+1)`` of ``x`` (..., T, H, D) by
    ``positions`` (T,) times the frequencies; cos and sin carry
    ``m(mscale) / m(mscale_all_dim)``."""
    r = model["rope"]
    ang = positions.astype(F32)[:, None] * rope_frequencies(model)[None, :]
    m = yarn_m(r["mscale"], r["factor"]) / yarn_m(r["mscale_all_dim"],
                                                  r["factor"])
    cos, sin = (jnp.cos(ang) * m)[:, None, :], (jnp.sin(ang) * m)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape)


def mm(x, w, quant=None):
    if quant is not None:
        x, w = quant(x), quant(w)
    return x @ w


def attention_half(model, p, x, quant=None):
    """``x + LatentAttn(n1(x))`` over (B, T, d), positions 0..T-1, in the
    expanded form, ``QUERY_BLOCK`` queries at a time."""
    b, t, d = x.shape
    h, nope, rope, vd = (model["n_heads"], model["qk_nope_head_dim"],
                         model["qk_rope_head_dim"], model["v_head_dim"])
    kr, eps, r = model["kv_lora_rank"], model["rms_eps"], model["rope"]
    pos = jnp.arange(t)
    y = rms_norm(x, p["norm1.scale"], eps)
    c_q = rms_norm(mm(y, p["q_a.w"], quant), p["q_norm.scale"], eps)
    q = mm(c_q, p["q_b.w"], quant).reshape(b, t, h, nope + rope)
    kva = mm(y, p["kv_a.w"], quant)
    c_kv = rms_norm(kva[..., :kr], p["kv_norm.scale"], eps)
    k_rope = rotate_pairs(model, kva[..., None, kr:], pos)     # (B, T, 1, r)
    kv = mm(c_kv, p["kv_b.w"], quant).reshape(b, t, h, nope + vd)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (b, t, h, rope))], -1)
    v = kv[..., nope:]
    q = jnp.concatenate([q[..., :nope],
                         rotate_pairs(model, q[..., nope:], pos)], -1)
    # the query's scale by position: 1 below the original context
    q = q * (1.0 + r["llama_4_scaling_beta"] * jnp.log1p(jnp.floor(
        pos.astype(F32) / r["original_max_position_embeddings"]))
             )[None, :, None, None]
    scale = (nope + rope) ** -0.5 * yarn_m(r["mscale_all_dim"],
                                           r["factor"]) ** 2

    def some_queries(args):
        qb, pb = args                           # (B, Q, H, D), (Q,)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        s = jnp.where((pos[None, :] <= pb[:, None])[None, None], s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    blk = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    n = t // blk
    o = jax.lax.map(some_queries, (
        q.reshape(b, n, blk, h, nope + rope).swapaxes(0, 1),
        pos.reshape(n, blk)))
    o = o.swapaxes(0, 1).reshape(b, t, h * vd)
    return x + mm(o, p["attn_out.w"], quant)


def gated(y, w_gate, w_up, w_down, quant=None):
    return mm(jax.nn.silu(mm(y, w_gate, quant)) * mm(y, w_up, quant),
              w_down, quant)


def combine_weights(model, p, y, quant=None):
    """(B, T, held): each token's weight on each expert held here; 0 where
    the expert is not among the token's ``top_k`` of ALL the layer's
    experts.  Softmax in float32 over all of them, the ``top_k`` largest
    divided by their sum, times ``routed_scale``."""
    probs = jax.nn.softmax(mm(y, p["router.w"], quant), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, model["top_k"])
    w = top_p / top_p.sum(-1, keepdims=True) * model["routed_scale"]
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
    """One layer; ``p`` holds its tensors by the names of ``LAYER``, already
    float32.  ``i`` (traced) is not read: every layer is alike."""
    x = attention_half(model, p, x, quant)
    y = rms_norm(x, p["norm2.scale"], model["rms_eps"])
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


def matmul_params(model: dict) -> float:
    """Parameters a token meets in a matrix product: attention's five
    projections, the shared expert, the router, of its ``top_k`` routed
    experts the share that is held here IN EXPECTATION (``top_k * held /
    total`` experts a token: uniform routing is assumed, the counters say
    what ran), and the slice of the head held here."""
    per_layer = (_size(model, ATTENTION) + _size(model, SHARED)
                 + _size(model, ("router.w",))
                 + model["top_k"] * model["experts_held"]
                 / model["experts_total"] * expert_params(model))
    return model["n_layers"] * per_layer + _size(model, ("head.w",))


def attention_flops(model: dict, context):
    """One token's scores and values over ``context`` keys, all layers, in
    the EXPANDED form (``qk_head_dim`` a score, ``v_head_dim`` a value): the
    lesser for prefill and less than what the absorbed decode executes, so
    a share of the peak counted from it is a lower bound."""
    per_key = 2.0 * model["n_heads"] * (
        model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
        + model["v_head_dim"])
    return model["n_layers"] * per_key * context


def cache_bytes_per_token(model: dict) -> int:
    """The latent row ``[c_kv | k_rope]`` in every layer."""
    return (model["n_layers"]
            * (model["kv_lora_rank"] + model["qk_rope_head_dim"])
            * counts.dtype_bytes(model["compute_dtype"]))


def expert_counters(obs) -> dict | None:
    """What the window's ticks did at the experts, from the program's
    cumulative counters as the scheduler stamps them on its ``retire`` spans
    (``obs["spans"]``): last reading minus first.  None where no span
    carries them (a program without the counters, or under two readings)."""
    seen = [a for n, _t, _d, a in (obs or {}).get("spans", ())
            if n == "retire" and "decode_ticks_counted" in a]
    if len(seen) < 2:
        return None
    return {k: seen[-1][k] - seen[0][k] for k in seen[0]
            if isinstance(seen[0][k], int) and k != "tick"}


def decode_weight_bytes(model: dict, obs=None) -> float:
    """Bytes a decode tick has to read of the weights: everything outside
    the routed experts and the embedding table, plus the held experts that
    the window's ticks reached (``experts_reached`` a tick, summed over
    layers, from the counters; every held expert where there are none)."""
    width = counts.dtype_bytes(model["param_dtype"])
    fixed = (weights.n_params(model) - _size(model, ("embed",))
             - model["n_layers"] * _size(model, EXPERTS))
    seen = expert_counters(obs)
    if seen and seen["decode_ticks_counted"]:
        reached = seen["experts_reached"] / seen["decode_ticks_counted"]
    else:
        reached = model["n_layers"] * model["experts_held"]
    return (fixed + reached * expert_params(model)) * width
