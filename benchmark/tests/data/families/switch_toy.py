"""Family ``switch_toy``: the dense family's block with the program's
Switch-style expert feed-forward in the feed-forward's place (top-1 routing
over ``n_experts`` two-matrix experts, the chosen expert's output weighted by
its raw router probability).  A toy for the CPU rehearsal, added to a
temporary copy of ``benchmark/`` as this one file: the proof that a second kind
of block is a family module plus the configuration that names it.

The program drops a token that overflows its expert's capacity; the
configuration's ``capacity_factor`` equals ``n_experts``, at which every expert
has room for every token and nothing drops, so the reference knows no
capacity.  On the ``data`` mesh the trainer's loss carries no load-balance
term (``parallel/data_parallel.py`` takes the model's logits alone), so the
reference's has none either.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from benchmark.families import dense
from benchmark.harness import weights
from benchmark.reducers import counts

# ---- 1. model keys -----------------------------------------------------------

MODEL_KEYS = (*dense.MODEL_KEYS, "n_experts", "capacity_factor")

# ---- 2. tensors --------------------------------------------------------------

ATTN = ("ln1.scale", "ln1.bias", "qkv.w", "qkv.b", "attn_out.w", "attn_out.b",
        "ln2.scale", "ln2.bias")
EXPERTS = ("router.w", "experts.w_in", "experts.b_in", "experts.w_out",
           "experts.b_out")

outer_shapes = dense.outer_shapes


def layer_shapes(model: dict, i: int) -> dict:
    s = dense.shapes(model)
    e, d, ff = model["n_experts"], model["d_model"], model["d_ff"]
    return {**{n: s[n] for n in ATTN},
            "router.w": (d, e), "experts.w_in": (e, d, ff),
            "experts.b_in": (e, ff), "experts.w_out": (e, ff, d),
            "experts.b_out": (e, d)}


def init_tensor(model: dict, key, name: str, shape, dtype):
    if name not in EXPERTS:
        return dense.init_tensor(model, key, name, shape, dtype)
    fan_in = model["d_ff"] if name.endswith("_out") else model["d_model"]
    bound = 1.0 / math.sqrt(fan_in)
    return jax.random.uniform(key, shape, jnp.float32, -bound,
                              bound).astype(dtype)


leaves = dense.leaves

# ---- 3. the program adapter --------------------------------------------------


def program_model(model: dict):
    from neural_networks_parallel_training_with_mpi_tpu.models import (
        Transformer,
    )

    return Transformer(dataclasses.replace(
        dense.transformer_config(model), moe_experts=model["n_experts"],
        moe_capacity_factor=float(model["capacity_factor"])))


def train_flags(model: dict, job: dict, seed: int, out_dir) -> list:
    return dense.train_flags(model, job, seed, out_dir) + [
        "--moe_experts", str(model["n_experts"]),
        "--moe_capacity_factor", str(model["capacity_factor"])]


def to_program_layer(model: dict, p: dict, i: int) -> dict:
    blk = {n: (dense._ln if n.startswith("ln") else dense._lin)(p, n)
           for n in ("ln1", "qkv", "attn_out", "ln2")}
    blk["moe"] = {"gate": {"w": p["router.w"]},
                  "experts": {n.split(".")[1]: p[n] for n in EXPERTS[1:]}}
    return blk


to_program_outer = dense.to_program_outer
split_program = dense.split_program
outer_leaves = dense.outer_leaves


def to_program(model: dict, outer: dict, layers: list) -> dict:
    return {**to_program_outer(model, outer),
            "blocks": [to_program_layer(model, p, i)
                       for i, p in enumerate(layers)]}


def layer_leaves(model: dict, blk: dict) -> dict:
    flat = {f"{n}.{part}": blk[n][part]
            for n in ("ln1", "qkv", "attn_out", "ln2")
            for part in (("scale", "bias") if n.startswith("ln")
                         else ("w", "b"))}
    flat["router.w"] = blk["moe"]["gate"]["w"]
    flat.update({n: blk["moe"]["experts"][n.split(".")[1]]
                 for n in EXPERTS[1:]})
    return leaves(model, flat)


# ---- 4. the plain reference --------------------------------------------------

embed, head_logits = dense.embed, dense.head_logits


def block(model, p, x, i, quant=None):
    """x + Attn(LN(x)), then x + p_e(y) * Expert_e(y) for the one expert ``e``
    the router scores highest at y = LN(x).  Every expert is computed for
    every token and the chosen one picked: plain, and a toy can afford it."""
    x = dense.attention_half(model, p, x, quant)
    y = dense.layer_norm(x, p["ln2.scale"], p["ln2.bias"], model["ln_eps"])
    probs = jax.nn.softmax(y @ p["router.w"], axis=-1)          # (B, T, E)
    pick = jax.nn.one_hot(probs.argmax(-1), model["n_experts"])
    q = quant or (lambda a: a)
    h = dense.gelu_tanh(jnp.einsum("btd,edf->btef", q(y), q(p["experts.w_in"]))
                        + p["experts.b_in"])
    out = (jnp.einsum("btef,efd->bted", q(h), q(p["experts.w_out"]))
           + p["experts.b_out"])
    return x + (out * (pick * probs)[..., None]).sum(2)


# ---- 5. counts ---------------------------------------------------------------


def matmul_params(model: dict) -> int:
    """What every token meets in a matrix product: the attention's two
    projections, the router, ONE expert's two matrices, and the head."""
    d, ff, e = model["d_model"], model["d_ff"], model["n_experts"]
    s = dense.shapes(model)
    per_layer = (math.prod(s["qkv.w"]) + math.prod(s["attn_out.w"]) + d * e
                 + 2 * d * ff)
    return model["n_layers"] * per_layer + math.prod(s["head.w"])


attention_flops = dense.attention_flops
cache_bytes_per_token = dense.cache_bytes_per_token


def decode_weight_bytes(model: dict, obs=None) -> int:
    """Everything but the embedding tables; of the experts, as many as the
    tick's streams can reach (``obs["slots"]`` where the harness observed
    it, else all of them)."""
    s = dense.shapes(model)
    e = model["n_experts"]
    tables = math.prod(s["embed"]) + (math.prod(s["pos"]) if "pos" in s else 0)
    one_expert = 2 * model["d_model"] * model["d_ff"] + model["d_ff"] \
        + model["d_model"]
    idle = e - min(e, obs["slots"]) if obs else 0
    return ((weights.n_params(model) - tables
             - model["n_layers"] * idle * one_expert)
            * counts.dtype_bytes(model["param_dtype"]))
