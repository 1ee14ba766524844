"""Family ``olmo_hybrid``: a post-norm decoder whose layers are of two kinds
(Olmo-Hybrid, Hugging Face ``model_type`` ``olmo_hybrid``): in ``layer_types``
a ``linear_attention`` layer mixes its tokens by a Gated DeltaNet in place of
attention (Yang et al., arXiv:2412.06464, as FLA's ``GatedDeltaNet``), a
``full_attention`` layer by multi-head attention without positions.

One layer is ``x <- x + n1(Mix(x))``, then ``x <- x + n2(MLP(x))`` (Olmo 2's
order: the norms on each half's output, none on its input), ``n`` RMSNorm:

* ``Mix`` of a full layer: ``q = x W_q``, ``k = x W_k``, ``v = x W_v``, no
  biases; ``q`` RMS-normed over its whole projection (one scale a lane), ``k``
  likewise; no rotation (``rope_theta`` null); query head ``n`` reads
  key/value head ``n // (n_heads / n_kv_heads)``; scores ``q . k /
  sqrt(head_dim)``, causal, softmax in float32; then ``W_o``;
* ``Mix`` of a linear layer, with ``H`` heads of keys ``dk`` and values
  ``dv``: ``[q | k | v] = silu(conv(x [W_q | W_k | W_v]))``, three causal
  depthwise convolutions of ``linear_conv_kernel_dim`` taps without bias (tap
  ``taps - 1`` is the token's own input); ``q``, ``k`` L2-normed a head
  (``x / sqrt(sum(x^2) + 1e-6)``), ``q`` times ``1 / sqrt(dk)``; ``beta =
  2 sigmoid(x W_b)`` (``linear_allow_neg_eigval``; ``sigmoid`` alone
  otherwise), ``alpha = exp(-exp(A_log) softplus(x W_a + dt_bias))``, a head;
  ``S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T`` from a
  zero state, ``o_t = S_t^T q_t``; ``o <- RMSNorm_dv(o) * silu(x W_g)`` a head
  (one weight of ``dv`` for all heads), then ``W_out``;
* ``MLP(u) = (silu(u W_gate) * (u W_up)) W_down``;
* outside the layers: the embedded tokens as they are, a final RMSNorm, the
  head untied.

Everything the benchmark knows about this kind of block, in the five parts
``benchmark/README.md`` lists.  The reference imports nothing from the program
and runs the delta rule as it is written: one step a token, no chunking, so it
shares no algebra with the program's chunked form.

Tensors are named flat; matrices are stored ``(in, out)``; ``qkv.w`` is laid
out ``[q | k | v]`` with heads contiguous, ``gdn_in.w`` ``[q | k | v | g | b |
a]`` (the six projections of a linear layer side by side) and ``conv.w``
``(taps, channels)`` over ``[q | k | v]`` (the three convolutions side by
side).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..harness import weights
from ..reducers import counts

F32 = jnp.float32
LINEAR, FULL = "linear_attention", "full_attention"
L2_EPS = 1e-6

# ---- 1. the model keys a configuration's ``mapping`` must spell -------------

# ``head_dim`` is ``d_model / n_heads``
MODEL_KEYS = ("vocab_size", "d_model", "n_layers", "n_heads", "n_kv_heads",
              "layer_types", "d_ff", "gdn_key_heads", "gdn_value_heads",
              "gdn_key_dim", "gdn_value_dim", "gdn_conv", "gdn_neg_eigval",
              "max_seq_len", "rms_eps", "param_dtype", "compute_dtype")
# the tile the counts of the chunked rule are taken at: FLA's, an
# implementation's and not mathematics, so that the count does not move with
# what implements the rule
CHUNK = 64


def layer_kinds(model: dict) -> list:
    """``layer_types`` of the layers held here (the first ``n_layers``)."""
    kinds = list(model["layer_types"][:model["n_layers"]])
    if len(kinds) != model["n_layers"] or set(kinds) - {LINEAR, FULL}:
        raise ValueError(f"configuration {model['config']!r}: layer_types "
                         f"gives no {LINEAR!r} or {FULL!r} kind to each of "
                         f"{model['n_layers']} layers")
    return kinds


def n_linear(model: dict) -> int:
    """Layers held here that carry recurrent state."""
    return layer_kinds(model).count(LINEAR)


def n_full(model: dict) -> int:
    """Layers held here that attend (and hold keys and values)."""
    return layer_kinds(model).count(FULL)


def head_dim(model: dict) -> int:
    if model["d_model"] % model["n_heads"]:
        raise ValueError(f"configuration {model['config']!r}: "
                         f"{model['n_heads']} heads do not tile d_model "
                         f"{model['d_model']}")
    return model["d_model"] // model["n_heads"]


def gdn_heads(model: dict) -> int:
    if model["gdn_key_heads"] != model["gdn_value_heads"]:
        raise ValueError(f"configuration {model['config']!r}: the delta "
                         "rule here has as many key heads as value heads")
    return model["gdn_key_heads"]


def gdn_widths(model: dict) -> tuple:
    """Widths of ``gdn_in.w``'s six segments [q | k | v | g | b | a]."""
    h = gdn_heads(model)
    qk, v = h * model["gdn_key_dim"], h * model["gdn_value_dim"]
    return (qk, qk, v, v, h, h)


# ---- 2. tensors: names, shapes, initialisation ------------------------------

OUTER = ("embed", "norm_f.scale", "head.w")
ATTENTION = ("qkv.w", "attn_out.w")
MIXER_PROJ = ("gdn_in.w", "gdn_out.w")
FFN = ("ffn.w_gate", "ffn.w_up", "ffn.w_down")
# a layer's tensors in one order for both kinds; what a kind lacks keeps its
# place with None
LAYER = (*ATTENTION, "q_norm.scale", "k_norm.scale", "gdn_in.w", "conv.w",
         "dt_bias", "A_log", "gdn_norm.scale", "gdn_out.w", "norm1.scale",
         "norm2.scale", *FFN)
FULL_ONLY = (*ATTENTION, "q_norm.scale", "k_norm.scale")
LINEAR_ONLY = ("gdn_in.w", "conv.w", "dt_bias", "A_log", "gdn_norm.scale",
               "gdn_out.w")


def shapes(model: dict) -> dict:
    d, v, h, kv = (model["d_model"], model["vocab_size"], model["n_heads"],
                   model["n_kv_heads"])
    hd, ff, hs = head_dim(model), model["d_ff"], gdn_heads(model)
    vw = hs * model["gdn_value_dim"]
    return {"embed": (v, d), "norm_f.scale": (d,), "head.w": (d, v),
            "qkv.w": (d, (h + 2 * kv) * hd), "attn_out.w": (h * hd, d),
            "q_norm.scale": (h * hd,), "k_norm.scale": (kv * hd,),
            "gdn_in.w": (d, sum(gdn_widths(model))),
            "conv.w": (model["gdn_conv"], sum(gdn_widths(model)[:3])),
            "dt_bias": (hs,), "A_log": (hs,),
            "gdn_norm.scale": (model["gdn_value_dim"],),
            "gdn_out.w": (vw, d), "norm1.scale": (d,), "norm2.scale": (d,),
            "ffn.w_gate": (d, ff), "ffn.w_up": (d, ff), "ffn.w_down": (ff, d)}


def outer_shapes(model: dict) -> dict:
    s = shapes(model)
    return {n: s[n] for n in OUTER}


def layer_shapes(model: dict, i: int) -> dict:
    s = shapes(model)
    lacks = LINEAR_ONLY if layer_kinds(model)[i] == FULL else FULL_ONLY
    return {n: None if n in lacks else s[n] for n in LAYER}


def init_tensor(model: dict, key, name: str, shape, dtype):
    """Spreads under which every mechanism shows (PERF.md section 4 has the
    readings): the embedding N(0, 1); norms' scales 1 + 0.1 N(0, 1); each
    projection uniform with the bound ``sqrt(3 / fan_in)`` (its output has
    RMS 1 for an input of RMS 1; the post-norms make every branch's RMS their
    own whatever the weights); the convolutions as torch's ``Conv1d``
    (uniform +-1 / sqrt(taps)); FLA's ``A_log = log U[1, 16]`` and
    ``dt_bias`` the inverse softplus of a log-uniform draw in [1e-3, 1e-1],
    so that the heads' memories span a few tokens to thousands."""
    if name == "embed":
        x = jax.random.normal(key, shape, F32)
    elif name.endswith(".scale"):
        x = 1.0 + 0.1 * jax.random.normal(key, shape, F32)
    elif name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, F32, math.log(1e-3),
                                        math.log(1e-1)))
        x = dt + jnp.log(-jnp.expm1(-dt))
    elif name == "A_log":
        x = jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    elif name == "conv.w":
        bound = 1.0 / math.sqrt(shape[0])
        x = jax.random.uniform(key, shape, F32, -bound, bound)
    else:   # a projection (in, out)
        bound = math.sqrt(3.0 / shape[0])
        x = jax.random.uniform(key, shape, F32, -bound, bound)
    return x.astype(dtype)


def leaves(model: dict, tensors: dict) -> dict:
    """The leaves the comparison names: the tensors themselves."""
    return dict(tensors)


# ---- 3. the program adapter -------------------------------------------------

_NORMS = {"norm1": "ln1", "norm2": "ln2", "q_norm": "q_norm",
          "k_norm": "k_norm"}
_LINEAR = {"qkv.w": "qkv", "attn_out.w": "attn_out", "ffn.w_gate": "ff_gate",
           "ffn.w_up": "ff_in", "ffn.w_down": "ff_out"}


def attention_pattern(model: dict) -> str:
    """The program's kinds of layer: 'D' a delta-rule layer, 'G' a full
    one."""
    return "".join("D" if k == LINEAR else "G" for k in layer_kinds(model))


def transformer_config(model: dict):
    try:
        from neural_networks_parallel_training_with_mpi_tpu.models import (
            TransformerConfig,
        )

        return TransformerConfig(
            vocab_size=model["vocab_size"], max_seq_len=model["max_seq_len"],
            n_layers=model["n_layers"], d_model=model["d_model"],
            n_heads=model["n_heads"], n_kv_heads=model["n_kv_heads"],
            d_ff=model["d_ff"], activation="swiglu", pos_encoding="rope",
            rope_global=False, norm="rmsnorm", norm_eps=model["rms_eps"],
            use_bias=False, qk_norm=True, qk_norm_whole=True,
            post_norm=True, attention_pattern=attention_pattern(model),
            gdn_heads=gdn_heads(model), gdn_key_dim=model["gdn_key_dim"],
            gdn_value_dim=model["gdn_value_dim"], gdn_conv=model["gdn_conv"],
            gdn_neg_eigval=bool(model["gdn_neg_eigval"]),
            param_dtype=jnp.dtype(model["param_dtype"]),
            compute_dtype=jnp.dtype(model["compute_dtype"]))
    except (ImportError, TypeError) as e:
        # a program from before the delta-rule layers: say what is missing
        # and stop, before any weight is made
        raise SystemExit(
            f"benchmark: the program in this checkout cannot build "
            f"configuration {model['config']!r} (family olmo_hybrid): it "
            f"lacks Gated DeltaNet layers in place of attention "
            f"(attention_pattern 'D', gdn_heads), the norms on each half's "
            f"output (post_norm) or the norm of q and k over the whole "
            f"projection (qk_norm_whole) ({type(e).__name__}: {e})"
        ) from None


def program_model(model: dict):
    from neural_networks_parallel_training_with_mpi_tpu.models import (
        Transformer,
    )

    return Transformer(transformer_config(model))


def train_flags(model: dict, job: dict, seed: int, out_dir) -> list:
    raise SystemExit(
        f"benchmark: configuration {model['config']!r} (family olmo_hybrid) "
        "is served only: one 16-layer stage does not train on one chip at "
        "the floors (PERF.md section 4)")


def to_program_layer(model: dict, p: dict, i: int) -> dict:
    blk = {prog: {"scale": p[f"{mine}.scale"]}
           for mine, prog in _NORMS.items() if f"{mine}.scale" in p}
    blk.update({prog: {"w": p[mine]} for mine, prog in _LINEAR.items()
                if mine in p})
    if "gdn_in.w" in p:
        blk["gdn"] = {"in_proj": {"w": p["gdn_in.w"]},
                      "conv": {"w": p["conv.w"]},
                      "dt_bias": p["dt_bias"], "A_log": p["A_log"],
                      "norm": {"scale": p["gdn_norm.scale"]},
                      "out_proj": {"w": p["gdn_out.w"]}}
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
           for mine, prog in _NORMS.items() if prog in blk}
    out.update({mine: blk[prog]["w"] for mine, prog in _LINEAR.items()
                if prog in blk})
    if "gdn" in blk:
        g = blk["gdn"]
        out.update({"gdn_in.w": g["in_proj"]["w"], "conv.w": g["conv"]["w"],
                    "dt_bias": g["dt_bias"], "A_log": g["A_log"],
                    "gdn_norm.scale": g["norm"]["scale"],
                    "gdn_out.w": g["out_proj"]["w"]})
    return out


# ---- 4. the plain reference: float32 ``jax.numpy`` --------------------------
# No cache, no kernels, no batching, no chunking, nothing imported from the
# program.  Every caller runs it under
# ``jax.default_matmul_precision("highest")``.  ``quant`` is the control's
# hook (``reference/control.py``), applied to both operands of every
# projection (the delta-rule layers' two among them); the rule stays float32.
# The second control, for the state's precision, is a key no configuration
# sets: ``model["gdn_state_dtype"]`` "bfloat16" rounds the state to that type
# once a token (``tools/serve_control_reading.py --controls state_bf16``).

QUERY_BLOCK = 256       # attention is computed this many queries at a time


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def l2_norm(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + L2_EPS)


def mm(x, w, quant=None):
    if quant is not None:
        x, w = quant(x), quant(w)
    return x @ w


def attention(model, p, x, quant=None):
    """A full layer's ``Attn(x)`` over (B, T, d), positions 0..T-1 and none
    of them rotated into q or k, ``QUERY_BLOCK`` queries at a time."""
    b, t, d = x.shape
    h, kv, hd = model["n_heads"], model["n_kv_heads"], head_dim(model)
    g, eps = h // kv, model["rms_eps"]
    pos = jnp.arange(t)
    qkv = mm(x, p["qkv.w"], quant)
    q = rms_norm(qkv[..., :h * hd], p["q_norm.scale"], eps)
    k = rms_norm(qkv[..., h * hd:(h + kv) * hd], p["k_norm.scale"], eps)
    v = qkv[..., (h + kv) * hd:].reshape(b, t, kv, hd)
    q = q.reshape(b, t, kv, g, hd)      # query head n reads kv head n // g
    k = k.reshape(b, t, kv, hd)
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


def beta_of(model, b):
    """``beta`` from ``x W_b``: in (0, 2) where the configuration allows a
    negative eigenvalue, in (0, 1) otherwise."""
    beta = jax.nn.sigmoid(b)
    return 2.0 * beta if model["gdn_neg_eigval"] else beta


def decay_of(model, p, a):
    """``alpha`` from ``x W_a``: ``exp(-exp(A_log) softplus(a + dt_bias))``."""
    return jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"]))


def delta_rule(q, k, v, beta, alpha, state_dtype=F32):
    """``S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T``,
    ``o_t = S_t^T q_t``, one step a token from a zero state, as it is
    written.  ``q``, ``k`` (B, T, H, dk), ``v`` (B, T, H, dv), ``beta``,
    ``alpha`` (B, T, H) -> ``o`` (B, T, H, dv).  Under another
    ``state_dtype`` (the control) ``S_t`` is rounded to it once a token, by
    ``reduce_precision``: a compiler may drop a cast to a narrower type and
    back as excess precision, and this rounding is the point."""
    b, _t, h, dk = k.shape
    eye = jnp.eye(dk, dtype=F32)
    bits = jnp.finfo(state_dtype)

    def step(s, now):
        q_t, k_t, v_t, b_t, a_t = now   # (B, H, dk), ., (B, H, dv), (B, H)
        kk = k_t[..., :, None] * k_t[..., None, :]          # (B, H, dk, dk)
        keep = a_t[..., None, None] * (eye - b_t[..., None, None] * kk)
        s = (jnp.einsum("bhij,bhjv->bhiv", keep, s)
             + b_t[..., None, None] * k_t[..., :, None] * v_t[..., None, :])
        if state_dtype != F32:
            s = jax.lax.reduce_precision(s, bits.nexp, bits.nmant)
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    _s, o = jax.lax.scan(
        step, jnp.zeros((b, h, dk, v.shape[-1]), F32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, beta, alpha)))
    return jnp.moveaxis(o, 0, 1)


def gdn(model, p, x, quant=None):
    """A linear layer's ``Mix(x)`` over (B, T, d) from a zero state."""
    b, t, _ = x.shape
    h, dk, dv = gdn_heads(model), model["gdn_key_dim"], model["gdn_value_dim"]
    taps, cuts = model["gdn_conv"], np.cumsum(gdn_widths(model))[:-1]
    proj = mm(x, p["gdn_in.w"], quant)
    qs, ks, vs, gate, bb, aa = jnp.split(proj, cuts, axis=-1)
    xqkv = jnp.concatenate([qs, ks, vs], -1)
    before = jnp.pad(xqkv, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = jax.nn.silu(sum(before[:, j:j + t] * p["conv.w"][j]
                           for j in range(taps)))
    q = l2_norm(conv[..., :h * dk].reshape(b, t, h, dk)) * dk ** -0.5
    k = l2_norm(conv[..., h * dk:2 * h * dk].reshape(b, t, h, dk))
    v = conv[..., 2 * h * dk:].reshape(b, t, h, dv)
    o = delta_rule(q, k, v, beta_of(model, bb), decay_of(model, p, aa),
                   jnp.dtype(model.get("gdn_state_dtype", "float32")))
    o = rms_norm(o, p["gdn_norm.scale"], model["rms_eps"])
    o = o.reshape(b, t, h * dv) * jax.nn.silu(gate)
    return mm(o, p["gdn_out.w"], quant)


def mlp(model, p, u, quant=None):
    return mm(jax.nn.silu(mm(u, p["ffn.w_gate"], quant))
              * mm(u, p["ffn.w_up"], quant), p["ffn.w_down"], quant)


def block(model, p, x, i, quant=None):
    """Layer ``i`` (traced or not: its kind is read off the tensors ``p``
    holds, by the names of ``LAYER``, already float32)."""
    eps = model["rms_eps"]
    mix = gdn(model, p, x, quant) if "gdn_in.w" in p \
        else attention(model, p, x, quant)
    x = x + rms_norm(mix, p["norm1.scale"], eps)
    return x + rms_norm(mlp(model, p, x, quant), p["norm2.scale"], eps)


def embed(model, outer, ids):
    return outer["embed"].astype(F32)[ids]


def head_logits(model, outer, x, quant=None):
    y = rms_norm(x, outer["norm_f.scale"].astype(F32), model["rms_eps"])
    return mm(y, outer["head.w"].astype(F32), quant)


# ---- 5. counts: operations and bytes from shapes ----------------------------


def _size(model: dict, names) -> int:
    s = shapes(model)
    return sum(math.prod(s[n]) for n in names)


def matmul_params(model: dict) -> int:
    """Parameters a token meets in a matrix product: each layer's mixer
    (attention's projections or the delta rule's) and feed-forward, and the
    head."""
    return (n_full(model) * _size(model, ATTENTION)
            + n_linear(model) * _size(model, MIXER_PROJ)
            + model["n_layers"] * _size(model, FFN)
            + _size(model, ("head.w",)))


def recurrence_flops(model: dict) -> float:
    """One token's delta rule in ONE linear layer, counted in its chunked
    form at the tile ``CHUNK`` (C): ``K K^T`` and ``Q K^T`` (2 C dk each), the
    triangular solve applied to ``K`` and ``V`` counted as a product (2 C
    (dk + dv)), ``W S``, ``exp(G) Q S`` and the tile's state (2 dk dv each),
    and the intra-tile ``P U`` (2 C dv), a head."""
    c, dk, dv = CHUNK, model["gdn_key_dim"], model["gdn_value_dim"]
    return gdn_heads(model) * (4.0 * c * dk + 2.0 * c * (dk + dv)
                               + 6.0 * dk * dv + 2.0 * c * dv)


def attention_flops(model: dict, context):
    """One token's scores and values over ``context`` keys (a number or an
    array of them) in the full layers, plus the delta rule's constant in the
    linear layers."""
    per_key = 2.0 * model["n_heads"] * 2 * head_dim(model)
    return (n_full(model) * per_key * context
            + n_linear(model) * recurrence_flops(model))


def kv_row_bytes(model: dict) -> int:
    """K and V of one token in ONE full layer."""
    return (2 * model["n_kv_heads"] * head_dim(model)
            * counts.dtype_bytes(model["compute_dtype"]))


def cache_bytes_per_token(model: dict) -> int:
    """The full layers alone hold keys and values."""
    return n_full(model) * kv_row_bytes(model)


def state_bytes(model: dict) -> int:
    """One stream's float32 state in ONE linear layer (the convolutions'
    tails, 3 % of it, are left out)."""
    return (gdn_heads(model) * model["gdn_key_dim"] * model["gdn_value_dim"]
            * 4)


def decode_weight_bytes(model: dict, obs=None) -> float:
    """Bytes a decode tick has to move beside the keys and values: the
    layers and the head once (everything but the embedding table), PLUS the
    recurrent state read and written once a linear layer for the mean number
    of decoding streams a tick (``obs["decode_stream_ticks"] /
    obs["decode_ticks"]``; none where nothing was observed).  The state is
    not a weight, but every tick has to move those bytes whatever implements
    the update, so they are routed through this hook, as
    ``ssm_attn_parallel`` routes its mixer's, and belong in
    ``decode_hbm_share.serve``, the share of the whole step."""
    fixed = ((weights.n_params(model) - _size(model, ("embed",)))
             * counts.dtype_bytes(model["param_dtype"]))
    streams = (obs["decode_stream_ticks"] / obs["decode_ticks"]
               if obs and obs.get("decode_ticks") else 0.0)
    return fixed + streams * 2.0 * n_linear(model) * state_bytes(model)
