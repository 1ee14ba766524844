"""Family ``ssm_attn_parallel``: a pre-norm decoder whose every block runs a
Mamba-2 mixer BESIDE grouped-query attention (Falcon-H1, arXiv:2507.22448),
with the model's fixed multipliers.

With ``h = rms(x)`` one layer is

    x <- x + a_out Attn(a_in h) + s_out Mixer(s_in h)
    x <- x + MLP(rms(x))

* ``Attn``: ``q = u W_q``, ``k = key_multiplier (u W_k)``, ``v = u W_v``, no
  biases; half-split rotary on q and k (pairs ``(i, i + head_dim / 2)``, angle
  ``pos * theta^(-i / (head_dim / 2))``); query head ``n`` reads key/value head
  ``n // (n_heads / n_kv_heads)``; scores ``q . k / sqrt(head_dim)``, causal,
  softmax in float32; then ``W_o``;
* ``Mixer(u)``: ``[z | xs | B | C | dt] = (u W_in) * m`` (``m`` holds the five
  ``ssm_multipliers`` over the segments ``H P | H P | G N | G N | H``); ``[xs |
  B | C] <- silu(conv(...) + b)``, a causal depthwise convolution of
  ``ssm_conv`` taps (tap ``ssm_conv - 1`` is the token's own input); ``dt <-
  softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head; for head ``j`` (width
  ``P``, group ``j // (H / G)``, state ``N``): ``S_t = exp(dt_t A) S_{t-1} +
  dt_t xs_t B_t^T``, ``y_t = S_t C_t + D xs_t``; then ``y <- y * silu(z)``,
  RMS-normed over each group's ``H P / G`` channels and scaled by a learned
  weight; then ``W_out``;
* ``MLP(u) = m_1 (silu(m_0 (u W_gate)) * (u W_up)) W_down``;
* outside the layers: ``embedding_multiplier`` on the embedded tokens, a
  final RMSNorm, ``lm_head_multiplier`` on the logits, the head untied.

Everything the benchmark knows about this kind of block, in the five parts
``benchmark/README.md`` lists.  The reference imports nothing from the program
and runs the recurrence as it is written: one step a token, no chunking.

Tensors are named flat; matrices are stored ``(in, out)``; ``qkv.w`` is laid
out ``[q | k | v]`` with heads contiguous, ``ssm_in.w`` ``[z | xs | B | C |
dt]``, ``conv.w`` ``(taps, channels)`` over ``[xs | B | C]``.
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
              "head_dim", "d_ff", "d_ssm", "ssm_heads", "ssm_head_dim",
              "ssm_state",
              "ssm_groups", "ssm_conv", "ssm_chunk", "max_seq_len", "rms_eps",
              "rope_theta", "embedding_multiplier", "lm_head_multiplier",
              "attention_in_multiplier", "attention_out_multiplier",
              "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
              "ssm_multipliers", "mlp_multipliers", "param_dtype",
              "compute_dtype")


# ---- 2. tensors: names, shapes, initialisation ------------------------------

OUTER = ("embed", "norm_f.scale", "head.w")
ATTENTION = ("qkv.w", "attn_out.w")
MIXER_PROJ = ("ssm_in.w", "ssm_out.w")
MIXER_REST = ("conv.w", "conv.b", "dt_bias", "A_log", "D", "ssm_norm.scale")
FFN = ("ffn.w_gate", "ffn.w_up", "ffn.w_down")
LAYER = ("norm1.scale", *ATTENTION, "ssm_in.w", *MIXER_REST, "ssm_out.w",
         "norm2.scale", *FFN)


def d_ssm(model: dict) -> int:
    """The mixer's inner width, which the heads have to tile."""
    if model["d_ssm"] != model["ssm_heads"] * model["ssm_head_dim"]:
        raise ValueError(
            f"configuration {model['config']!r}: d_ssm {model['d_ssm']} is "
            f"not ssm_heads {model['ssm_heads']} x ssm_head_dim "
            f"{model['ssm_head_dim']}")
    return model["d_ssm"]


def segments(model: dict) -> tuple:
    """Widths of the mixer projection's five segments [z | xs | B | C | dt]."""
    gn = model["ssm_groups"] * model["ssm_state"]
    return (d_ssm(model), d_ssm(model), gn, gn, model["ssm_heads"])


def shapes(model: dict) -> dict:
    d, v, h, kv, hd = (model["d_model"], model["vocab_size"],
                       model["n_heads"], model["n_kv_heads"],
                       model["head_dim"])
    ff, ds, hs = model["d_ff"], d_ssm(model), model["ssm_heads"]
    conv = ds + 2 * model["ssm_groups"] * model["ssm_state"]
    return {"embed": (v, d), "norm_f.scale": (d,), "head.w": (d, v),
            "norm1.scale": (d,), "qkv.w": (d, (h + 2 * kv) * hd),
            "attn_out.w": (h * hd, d), "ssm_in.w": (d, sum(segments(model))),
            "conv.w": (model["ssm_conv"], conv), "conv.b": (conv,),
            "dt_bias": (hs,), "A_log": (hs,), "D": (hs,),
            "ssm_norm.scale": (ds,), "ssm_out.w": (ds, d),
            "norm2.scale": (d,), "ffn.w_gate": (d, ff), "ffn.w_up": (d, ff),
            "ffn.w_down": (ff, d)}


def outer_shapes(model: dict) -> dict:
    s = shapes(model)
    return {n: s[n] for n in OUTER}


def layer_shapes(model: dict, i: int) -> dict:
    """Every layer holds both halves (``attn_layer_indices`` null)."""
    s = shapes(model)
    return {n: s[n] for n in LAYER}


# The spreads.  A model trained under these multipliers has weights that make
# up for them; seeded weights have to as well, or a multiplier of 0.011 on the
# keys leaves every softmax uniform and a multiplier of 0.0078 on the logits
# leaves nothing to compare.  So each projection ``y = mult (x W)`` is drawn
# uniform with the spread that gives ``y`` an RMS of ``gain`` for an ``x`` of
# RMS 1: bound ``sqrt(3) gain / (mult sqrt(fan_in))``, ``mult`` the product of
# the multipliers on the way from the normed input to ``y``, column by
# column.  ``gain`` is 1 but where what follows shrinks the signal: the
# attention's output averages many values (scores of RMS 1 over a few hundred
# keys leave about a tenth of a value's RMS), and the convolution feeds a
# SiLU whose output has to be large against the skip ``D xs`` for the state
# to show in ``y`` (PERF.md section 4 has the readings).
GAIN = {"attn_out.w": 2.0, "conv.w": 2.0}


def _column_multipliers(model: dict, name: str) -> np.ndarray | float:
    m = model
    if name == "qkv.w":
        h, kv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
        a = m["attention_in_multiplier"]
        return np.repeat([a, a * m["key_multiplier"], a],
                         [h * hd, kv * hd, kv * hd])
    if name == "ssm_in.w":
        return m["ssm_in_multiplier"] * np.repeat(
            np.asarray(m["ssm_multipliers"], np.float64), segments(m))
    return {"attn_out.w": m["attention_out_multiplier"],
            "ssm_out.w": m["ssm_out_multiplier"],
            "ffn.w_gate": m["mlp_multipliers"][0],
            "ffn.w_down": m["mlp_multipliers"][1],
            "head.w": m["lm_head_multiplier"]}.get(name, 1.0)


def init_tensor(model: dict, key, name: str, shape, dtype):
    if name == "embed":     # the embedded tokens have RMS 1
        x = jax.random.normal(key, shape, F32) / model["embedding_multiplier"]
    elif name.endswith(".scale"):
        x = 1.0 + 0.1 * jax.random.normal(key, shape, F32)
    elif name == "dt_bias":     # softplus(dt_bias) log-uniform in [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(key, shape, F32, math.log(1e-3),
                                        math.log(1e-1)))
        x = dt + jnp.log(-jnp.expm1(-dt))
    elif name == "A_log":
        x = jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    elif name == "D":
        x = jnp.ones(shape, F32)
    elif name == "conv.b":
        x = jax.random.uniform(key, shape, F32, -0.5, 0.5)
    else:   # a projection (in, out), or the convolution's taps (taps, chan)
        bound = (math.sqrt(3.0 / shape[0]) * GAIN.get(name, 1.0)
                 / np.asarray(_column_multipliers(model, name), np.float32))
        x = jax.random.uniform(key, shape, F32, -1.0, 1.0) * bound
    return x.astype(dtype)


def leaves(model: dict, tensors: dict) -> dict:
    """The leaves the comparison names: the tensors themselves."""
    return dict(tensors)


# ---- 3. the program adapter -------------------------------------------------

_NORMS = {"norm1": "ln1", "norm2": "ln2"}
_LINEAR = {"qkv.w": "qkv", "attn_out.w": "attn_out", "ffn.w_gate": "ff_gate",
           "ffn.w_up": "ff_in", "ffn.w_down": "ff_out"}


def transformer_config(model: dict):
    try:
        from neural_networks_parallel_training_with_mpi_tpu.models import (
            TransformerConfig,
        )

        return TransformerConfig(
            vocab_size=model["vocab_size"], max_seq_len=model["max_seq_len"],
            n_layers=model["n_layers"], d_model=model["d_model"],
            n_heads=model["n_heads"], n_kv_heads=model["n_kv_heads"],
            head_width=model["head_dim"], d_ff=model["d_ff"],
            activation="swiglu", pos_encoding="rope",
            rope_theta=float(model["rope_theta"]), norm="rmsnorm",
            norm_eps=model["rms_eps"], use_bias=False,
            ssm_heads=model["ssm_heads"], ssm_head_dim=model["ssm_head_dim"],
            ssm_state=model["ssm_state"], ssm_groups=model["ssm_groups"],
            ssm_conv=model["ssm_conv"], ssm_chunk=model["ssm_chunk"],
            embedding_multiplier=float(model["embedding_multiplier"]),
            lm_head_multiplier=float(model["lm_head_multiplier"]),
            attention_in_multiplier=float(model["attention_in_multiplier"]),
            attention_out_multiplier=float(
                model["attention_out_multiplier"]),
            key_multiplier=float(model["key_multiplier"]),
            ssm_in_multiplier=float(model["ssm_in_multiplier"]),
            ssm_out_multiplier=float(model["ssm_out_multiplier"]),
            ssm_multipliers=tuple(float(m)
                                  for m in model["ssm_multipliers"]),
            mlp_multipliers=tuple(float(m)
                                  for m in model["mlp_multipliers"]),
            param_dtype=jnp.dtype(model["param_dtype"]),
            compute_dtype=jnp.dtype(model["compute_dtype"]))
    except (ImportError, TypeError) as e:
        # a program from before the mixer: say what is missing and stop,
        # before any weight is made
        raise SystemExit(
            f"benchmark: the program in this checkout cannot build "
            f"configuration {model['config']!r} (family ssm_attn_parallel): "
            f"it lacks a state-space mixer beside the attention (ssm_heads) "
            f"or the model's multipliers ({type(e).__name__}: {e})"
        ) from None


def program_model(model: dict):
    from neural_networks_parallel_training_with_mpi_tpu.models import (
        Transformer,
    )

    return Transformer(transformer_config(model))


def train_flags(model: dict, job: dict, seed: int, out_dir) -> list:
    """The flags ``cli.main`` would parse for this model and job."""
    opt = job["optimizer"]
    csv = lambda xs: ",".join(str(x) for x in xs)              # noqa: E731
    return [
        "--dataset", "lm", "--arch", "transformer", "--loss", "cross_entropy",
        "--vocab_size", str(model["vocab_size"]),
        "--seq_len", str(job["seq_len"]),
        "--n_layers", str(model["n_layers"]),
        "--d_model", str(model["d_model"]),
        "--n_heads", str(model["n_heads"]),
        "--n_kv_heads", str(model["n_kv_heads"]),
        "--head_width", str(model["head_dim"]),
        "--d_ff", str(model["d_ff"]), "--ffn_activation", "swiglu",
        "--pos_encoding", "rope", "--rope_theta", str(model["rope_theta"]),
        "--norm", "rmsnorm", "--norm_eps", str(model["rms_eps"]), "--no-bias",
        "--ssm_heads", str(model["ssm_heads"]),
        "--ssm_head_dim", str(model["ssm_head_dim"]),
        "--ssm_state", str(model["ssm_state"]),
        "--ssm_groups", str(model["ssm_groups"]),
        "--ssm_conv", str(model["ssm_conv"]),
        "--ssm_chunk", str(model["ssm_chunk"]),
        "--embedding_multiplier", str(model["embedding_multiplier"]),
        "--lm_head_multiplier", str(model["lm_head_multiplier"]),
        "--attention_in_multiplier", str(model["attention_in_multiplier"]),
        "--attention_out_multiplier", str(model["attention_out_multiplier"]),
        "--key_multiplier", str(model["key_multiplier"]),
        "--ssm_in_multiplier", str(model["ssm_in_multiplier"]),
        "--ssm_out_multiplier", str(model["ssm_out_multiplier"]),
        "--ssm_multipliers", csv(model["ssm_multipliers"]),
        "--mlp_multipliers", csv(model["mlp_multipliers"]),
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
    blk.update({prog: {"w": p[mine]} for mine, prog in _LINEAR.items()})
    blk["ssm"] = {"in_proj": {"w": p["ssm_in.w"]},
                  "conv": {"w": p["conv.w"], "b": p["conv.b"]},
                  "dt_bias": p["dt_bias"], "A_log": p["A_log"], "D": p["D"],
                  "norm": {"scale": p["ssm_norm.scale"]},
                  "out_proj": {"w": p["ssm_out.w"]}}
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
    out.update({mine: blk[prog]["w"] for mine, prog in _LINEAR.items()})
    s = blk["ssm"]
    out.update({"ssm_in.w": s["in_proj"]["w"], "conv.w": s["conv"]["w"],
                "conv.b": s["conv"]["b"], "dt_bias": s["dt_bias"],
                "A_log": s["A_log"], "D": s["D"],
                "ssm_norm.scale": s["norm"]["scale"],
                "ssm_out.w": s["out_proj"]["w"]})
    return out


# ---- 4. the plain reference: float32 ``jax.numpy`` --------------------------
# No cache, no kernels, no batching, no chunking, nothing imported from the
# program.  Every caller runs it under
# ``jax.default_matmul_precision("highest")``.  ``quant`` is the control's
# hook (``reference/control.py``), applied to both operands of every
# projection (the mixer's two among them); the recurrence stays float32.

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


def attention(model, p, u, quant=None):
    """``Attn(u)`` over (B, T, d), positions 0..T-1, ``QUERY_BLOCK`` queries
    at a time: the keys times ``key_multiplier`` before the rotation."""
    b, t, d = u.shape
    h, kv, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    g = h // kv
    pos = jnp.arange(t)
    qkv = mm(u, p["qkv.w"], quant)
    q = qkv[..., :h * hd].reshape(b, t, h, hd)
    k = qkv[..., h * hd:(h + kv) * hd].reshape(b, t, kv, hd)
    v = qkv[..., (h + kv) * hd:].reshape(b, t, kv, hd)
    k = k * model["key_multiplier"]
    q, k = rotate(model, q, pos), rotate(model, k, pos)
    q = q.reshape(b, t, kv, g, hd)      # query head n reads kv head n // g
    scale = hd ** -0.5

    def some_queries(args):
        qb, pb = args                           # (B, Q, KV, G, D), (Q,)
        s = jnp.einsum("bqcgd,bkcd->bcgqk", qb, k) * scale
        seen = pb[:, None] >= pos[None, :]
        s = jnp.where(seen[None, None, None], s, -jnp.inf)
        return jnp.einsum("bcgqk,bkcd->bqcgd", jax.nn.softmax(s, -1), v)

    blk = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    n = t // blk
    o = jax.lax.map(some_queries, (
        q.reshape(b, n, blk, kv, g, hd).swapaxes(0, 1), pos.reshape(n, blk)))
    o = o.swapaxes(0, 1).reshape(b, t, h * hd)
    return mm(o, p["attn_out.w"], quant)


def gate(y, z):
    """``y * silu(z)``, before the norm (``mamba_norm_before_gate`` false)."""
    return y * jax.nn.silu(z)


def recurrence(model, p, xs, bm, cm, dt):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t xs_t B_t^T``, ``y_t = S_t C_t + D
    xs_t``, one step a token from a zero state.  ``xs`` (B, T, H, P), ``bm``
    / ``cm`` (B, T, G, N), ``dt`` (B, T, H) after its softplus."""
    b, _t, h, hp = xs.shape
    per = h // model["ssm_groups"]
    a = -jnp.exp(p["A_log"])

    def step(s, now):
        x_t, b_t, c_t, dt_t = now       # (B, H, P), (B, G, N) x2, (B, H)
        b_h, c_h = jnp.repeat(b_t, per, 1), jnp.repeat(c_t, per, 1)
        s = (jnp.exp(dt_t * a)[..., None, None] * s
             + (dt_t[..., None] * x_t)[..., None] * b_h[:, :, None, :])
        y = jnp.einsum("bhpn,bhn->bhp", s, c_h) + p["D"][:, None] * x_t
        return s, y

    _s, ys = jax.lax.scan(
        step, jnp.zeros((b, h, hp, model["ssm_state"]), F32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (xs, bm, cm, dt)))
    return jnp.moveaxis(ys, 0, 1)


def mixer(model, p, u, quant=None):
    """``Mixer(u)`` over (B, T, d) from a zero state."""
    b, t, _ = u.shape
    hs, hp = model["ssm_heads"], model["ssm_head_dim"]
    g, n, taps = model["ssm_groups"], model["ssm_state"], model["ssm_conv"]
    ds, gn = hs * hp, g * n
    proj = mm(u, p["ssm_in.w"], quant) * np.repeat(
        np.asarray(model["ssm_multipliers"], np.float32), segments(model))
    z, xbc, dt = (proj[..., :ds], proj[..., ds:2 * ds + 2 * gn],
                  proj[..., 2 * ds + 2 * gn:])
    before = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = p["conv.b"] + sum(before[:, k:k + t] * p["conv.w"][k]
                             for k in range(taps))
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :ds].reshape(b, t, hs, hp)
    bm = xbc[..., ds:ds + gn].reshape(b, t, g, n)
    cm = xbc[..., ds + gn:].reshape(b, t, g, n)
    y = recurrence(model, p, xs, bm, cm,
                   jax.nn.softplus(dt + p["dt_bias"]))
    y = gate(y.reshape(b, t, ds), z).reshape(b, t, g, ds // g)
    y = rms_norm(y, p["ssm_norm.scale"].reshape(g, ds // g),
                 model["rms_eps"]).reshape(b, t, ds)
    return mm(y, p["ssm_out.w"], quant)


def mlp(model, p, u, quant=None):
    m_gate, m_out = model["mlp_multipliers"]
    return m_out * mm(jax.nn.silu(m_gate * mm(u, p["ffn.w_gate"], quant))
                      * mm(u, p["ffn.w_up"], quant), p["ffn.w_down"], quant)


def block(model, p, x, i, quant=None):
    """Layer ``i`` (every layer is alike); ``p`` holds its tensors by the
    names of ``LAYER``, already float32."""
    h = rms_norm(x, p["norm1.scale"], model["rms_eps"])
    x = (x + model["attention_out_multiplier"] * attention(
            model, p, model["attention_in_multiplier"] * h, quant)
         + model["ssm_out_multiplier"] * mixer(
            model, p, model["ssm_in_multiplier"] * h, quant))
    return x + mlp(model, p, rms_norm(x, p["norm2.scale"], model["rms_eps"]),
                   quant)


def embed(model, outer, ids):
    return model["embedding_multiplier"] * outer["embed"].astype(F32)[ids]


def head_logits(model, outer, x, quant=None):
    y = rms_norm(x, outer["norm_f.scale"].astype(F32), model["rms_eps"])
    return model["lm_head_multiplier"] * mm(y, outer["head.w"].astype(F32),
                                            quant)


# ---- 5. counts: operations and bytes from shapes ----------------------------


def _size(model: dict, names) -> int:
    s = shapes(model)
    return sum(math.prod(s[n]) for n in names)


def matmul_params(model: dict) -> int:
    """Parameters a token meets in a matrix product: the attention's and the
    mixer's projections and the feed-forward in every layer, and the head."""
    return (model["n_layers"] * _size(model, (*ATTENTION, *MIXER_PROJ, *FFN))
            + _size(model, ("head.w",)))


def recurrence_flops(model: dict) -> float:
    """One token's recurrence in ONE layer, counted in its chunked form at
    the published tile ``ssm_chunk`` (Q), so that the count does not move
    with what implements it: ``C B^T`` (2 Q N G), its product with ``xs`` (2
    Q P a head), the tile's state and the read of the entering one (2 P N
    each a head)."""
    q, n, g = model["ssm_chunk"], model["ssm_state"], model["ssm_groups"]
    h, p = model["ssm_heads"], model["ssm_head_dim"]
    return 2.0 * q * n * g + h * (2.0 * q * p + 4.0 * p * n)


def attention_flops(model: dict, context):
    """One token's scores and values over ``context`` keys (a number or an
    array of them) plus its recurrence's constant, all layers."""
    per_key = 2.0 * model["n_heads"] * 2 * model["head_dim"]
    return model["n_layers"] * (per_key * context + recurrence_flops(model))


def kv_row_bytes(model: dict) -> int:
    """K and V of one token in ONE layer."""
    return (2 * model["n_kv_heads"] * model["head_dim"]
            * counts.dtype_bytes(model["compute_dtype"]))


def cache_bytes_per_token(model: dict) -> int:
    return model["n_layers"] * kv_row_bytes(model)


def state_bytes(model: dict) -> int:
    """One stream's float32 state in ONE layer (the convolution's tail, a
    hundredth of it, is left out)."""
    return d_ssm(model) * model["ssm_state"] * 4


def decode_weight_bytes(model: dict, obs=None) -> float:
    """Bytes a decode tick has to move beside the keys and values: the
    layers and the head once (everything but the embedding table), PLUS the
    recurrent state read and written once a layer for the mean number of
    decoding streams a tick (``obs["decode_stream_ticks"] /
    obs["decode_ticks"]``; none where nothing was observed).  The state is
    not a weight, but every tick has to move those bytes whatever implements
    the update, so they belong in ``decode_hbm_share.serve``, the share of
    the whole step."""
    fixed = ((weights.n_params(model) - _size(model, ("embed",)))
             * counts.dtype_bytes(model["param_dtype"]))
    streams = (obs["decode_stream_ticks"] / obs["decode_ticks"]
               if obs and obs.get("decode_ticks") else 0.0)
    return fixed + streams * 2.0 * model["n_layers"] * state_bytes(model)
