"""The one place that knows how the program spells a model.

Everything else in the benchmark speaks of a model as the flat dict a
configuration file maps to (``common.model_of``) and of tensors by the flat
names of ``weights.py``.  This module turns those into what the program takes:
its ``TransformerConfig``, the trainer's command-line flags, its parameter tree.
"""

from __future__ import annotations

from . import weights

ACTIVATIONS = {"gelu_tanh": "gelu"}      # the program's gelu is the tanh form
_BLOCK = ("ln1", "qkv", "attn_out", "ln2", "ff_in", "ff_out")


def transformer_config(model: dict):
    import jax.numpy as jnp

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


def train_flags(model: dict, job: dict, seed: int, out_dir) -> list:
    """The flags ``cli.main`` would parse for this model and job."""
    if model["ln_eps"] != 1e-5:
        raise ValueError("the program's LayerNorm has eps 1e-5 and no flag")
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


def to_program_layer(p: dict) -> dict:
    return {n: (_ln if n.startswith("ln") else _lin)(p, n) for n in _BLOCK}


def to_program_outer(outer: dict) -> dict:
    tree = {"embed": {"table": outer["embed"]}, "ln_f": _ln(outer, "ln_f"),
            "head": {"w": outer["head.w"]}}
    if "pos" in outer:
        tree["pos"] = {"table": outer["pos"]}
    return tree


def to_program(outer: dict, layers: list) -> dict:
    """The program's parameter tree from the benchmark's flat tensors."""
    return {**to_program_outer(outer),
            "blocks": [to_program_layer(p) for p in layers]}


def outer_leaves(tree: dict) -> dict:
    out = {"embed": tree["embed"]["table"], "head.w": tree["head"]["w"],
           "ln_f.scale": tree["ln_f"]["scale"],
           "ln_f.bias": tree["ln_f"]["bias"]}
    if "pos" in tree:
        out["pos"] = tree["pos"]["table"]
    return out


def layer_leaves(model: dict, blk: dict) -> dict:
    """One block of the program's tree -> {flat name: leaf}.  The fused qkv
    projection is split into its q, k and v columns: they are three tensors
    of the published model, and the key's bias has no gradient under
    softmax."""
    out = {}
    for n in _BLOCK:
        for part in (("scale", "bias") if n.startswith("ln") else ("w", "b")):
            if n == "qkv":
                for m, x in weights.split_qkv(model, blk[n][part]).items():
                    out[f"{m}.{part}"] = x
            else:
                out[f"{n}.{part}"] = blk[n][part]
    return out


def flat_names(model: dict, tree: dict) -> dict:
    """A tree shaped like the program's parameters -> {flat name: leaf}, with
    the names ``reference.train.leaf_norms`` gives (``L3.attn_out.w``)."""
    out = outer_leaves(tree)
    for i, blk in enumerate(tree["blocks"]):
        out.update({f"L{i}.{n}": x
                    for n, x in layer_leaves(model, blk).items()})
    return out
