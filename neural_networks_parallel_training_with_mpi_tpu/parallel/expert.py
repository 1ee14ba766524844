"""Expert-parallel (MoE) train step over the 'expert' mesh axis.

The reference has no MoE or alltoall communication (SURVEY.md §2.2/§2.3) —
this is an added TPU-native capability.  Layout:

* **Tokens** are batch-sharded over ``data x fsdp x expert`` — the expert
  axis's devices each carry their own batch slice, so the expert axis does
  double duty as extra data parallelism (the GShard arrangement).
* **Expert weights** (leaves under ``.../moe/experts``) are sharded over
  'expert' on their leading expert dim; gate and all other params are
  replicated.
* Each MoE layer performs one all_to_all to move routed token slots to the
  devices owning their experts and one to bring outputs home
  (models.moe.MoEFFN with ``expert_axis`` set) — the collective rides ICI.
* Gradient reduction mirrors the layout: expert-sharded grads psum over the
  token axes except 'expert'; replicated params psum over all token axes.

The loss is ``global_mean(task loss) + aux_weight * mean(load_balance)``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.transformer import Transformer
from ..ops import losses as losses_lib
from ..ops.optim import Optimizer
from ..train.state import TrainState
from .data_parallel import DATA_AXES

Pytree = Any
Batch = Dict[str, jax.Array]
EXPERT_AXIS = "expert"
# token (batch-dim) sharding for the MoE path: expert axis carries data too
TOKEN_AXES: Tuple[str, ...] = DATA_AXES + (EXPERT_AXIS,)


def _is_expert_path(path) -> bool:
    return any(getattr(k, "key", None) == "experts" for k in path)


def moe_param_specs(params: Pytree) -> Pytree:
    """Expert-stacked leaves (under an 'experts' subtree) -> P('expert');
    everything else replicated."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: P(EXPERT_AXIS) if _is_expert_path(path) else P(),
        params)


def moe_state_specs(optimizer: Optimizer, params: Pytree) -> TrainState:
    pspecs = moe_param_specs(params)
    if optimizer.state_specs is None:
        raise ValueError(f"{optimizer.name} lacks state_specs")
    return TrainState(step=P(), params=pspecs,
                      opt_state=optimizer.state_specs(pspecs, params))


def shard_moe_state(state: TrainState, mesh: Mesh,
                    optimizer: Optimizer) -> TrainState:
    specs = moe_state_specs(optimizer, state.params)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), state, specs)


def _moe_accumulate(micro_grads, params, batch: Batch, accum_steps: int):
    """Shared MoE microbatch accumulation: split the per-device batch rows
    into ``accum_steps`` microbatches and scan ``micro_grads`` over them,
    summing loss/count/grads in f32 and count-weighting the mean-style aux
    so the final aux is the token-weighted mean.  Returns
    ``(loss_sum, count, aux, grads)`` exactly like a single ``micro_grads``
    call (ulp-level f32 reassociation aside)."""
    if accum_steps <= 1:
        return micro_grads(params, batch)
    micro = {}
    for k, v in batch.items():
        rows = v.shape[0]
        if rows % accum_steps:
            raise ValueError(
                f"per-device batch rows {rows} (leaf {k!r}) not "
                f"divisible by accum_steps={accum_steps}")
        micro[k] = v.reshape(
            (accum_steps, rows // accum_steps) + v.shape[1:])

    def body(carry, mb):
        cs, cc, ca, cg = carry
        s, c, aux, g = micro_grads(params, mb)
        cg = jax.tree_util.tree_map(
            lambda a, b: a + b.astype(jnp.float32), cg, g)
        return (cs + s, cc + c, ca + aux * c, cg), None

    zeros = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    init = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.float32), zeros)
    (s, cnt, aux_w, grads), _ = lax.scan(body, init, micro)
    return s, cnt, aux_w / jnp.maximum(cnt, 1.0), grads


def _global_norm_clip(grads: Pytree, grad_clip: float, clip_axes):
    """Clip ``grads`` by the GLOBAL norm on a sharded layout:
    ``clip_axes(path)`` names the mesh axes a leaf's gradient is sharded
    over — its squared norm is psum'd over exactly those axes (grouped so
    each distinct axis set costs one psum) before the norms combine into
    the one true global norm every device agrees on."""
    partial_sq: Dict[Tuple[str, ...], jax.Array] = {}
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        axes = tuple(clip_axes(path))
        term = jnp.sum(jnp.square(g.astype(jnp.float32)))
        partial_sq[axes] = partial_sq.get(
            axes, jnp.zeros((), jnp.float32)) + term
    gsq = jnp.zeros((), jnp.float32)
    for axes, sq in partial_sq.items():
        gsq = gsq + (lax.psum(sq, axes) if axes else sq)
    scale = jnp.minimum(1.0, grad_clip / jnp.maximum(jnp.sqrt(gsq), 1e-12))
    return jax.tree_util.tree_map(
        lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), grads)


def _seq_active(mesh: Mesh, seq_axis) -> bool:
    return seq_axis is not None and int(mesh.shape.get(seq_axis, 1)) > 1


def _moe_token_axes(mesh: Mesh, seq_axis) -> Tuple[Tuple[str, ...],
                                                   Tuple[str, ...]]:
    """(token_axes, expert_leaf_axes) for one MoE layout: tokens ride
    data x fsdp x expert (x seq when active); expert-SHARDED leaves reduce
    over everything except 'expert' (they own their shard's grads).
    'tensor' never appears in either — tensor-sharded leaves own their
    shard locally and tensor-replicated leaves carry identical grads on
    every tensor rank (the f/g conjugate ops guarantee it)."""
    tail = (seq_axis,) if _seq_active(mesh, seq_axis) else ()
    return TOKEN_AXES + tail, DATA_AXES + tail


def _moe_grad_psum(grads: Pytree, total, token_axes, expert_axes) -> Pytree:
    """THE single gradient-reduction rule for every MoE layout (plain EP,
    EP x TP, their seq-composed forms): expert-sharded leaves psum over
    ``expert_axes``, everything else over ``token_axes``, normalized by
    the global token count."""
    return jax.tree_util.tree_map_with_path(
        lambda path, g: lax.psum(
            g, expert_axes if _is_expert_path(path) else token_axes)
        / total, grads)


def _moe_batch_specs(batch_keys, token_axes, seq_axis) -> dict:
    """Batch specs for the MoE paths: rows over the token axes; with an
    active seq axis, x/y additionally shard dim 1 (mask stays per-row).

    Unlike ``spmd.batch_specs`` this works from KEYS (the MoE builders
    derive their shard_map specs before seeing a batch), so it cannot
    inspect ranks — with seq active, only the (B, T) x/y + per-row mask
    contract is derivable from names alone, and other keys are rejected
    loudly here instead of failing inside shard_map tracing."""
    if seq_axis:
        extra = [k for k in batch_keys if k not in ("x", "y", "mask")]
        if extra:
            raise ValueError(
                f"seq-sharded MoE specs are derived from key names and "
                f"only know x/y (B, T) and mask (B,); got extra keys "
                f"{extra} — pass specs explicitly or drop the keys")
    specs = {}
    for k in batch_keys:
        if seq_axis and k != "mask":
            specs[k] = P(token_axes, seq_axis)
        else:
            specs[k] = P(token_axes)
    return specs


def make_moe_train_step(model: Transformer, optimizer: Optimizer, mesh: Mesh,
                        loss_name: str = "cross_entropy",
                        aux_weight: float = 0.01,
                        donate: bool = True,
                        batch_keys: Tuple[str, ...] = ("x", "y", "mask"),
                        grad_clip: float = 0.0,
                        accum_steps: int = 1,
                        seq_axis=None):
    """(state, batch) -> (state, metrics) jitted over data x fsdp x expert
    (x seq with ``seq_axis`` — long-context MoE: ring/ulysses attention
    over 'seq' composed with the all_to_all expert dispatch; the model's
    ``attention`` must then be a seq-sharded impl and every token
    reduction additionally spans the seq axis).

    ``metrics`` = {"loss": task loss, "aux": mean load-balance loss}.  The
    model's ``moe_expert_axis`` must equal 'expert' when the mesh's expert
    axis is >1 (so MoEFFN issues the all_to_alls).

    ``grad_clip`` clips by the *global* norm: expert-sharded leaves' squared
    norms are psum'd over 'expert' first — do NOT wrap ``optimizer`` in
    ``optim.with_clipping`` here (shard-local norms would desynchronize the
    replicated params across the expert axis).
    """
    c = model.cfg
    c.require_plain_block("the expert-parallel step (parallel/expert.py)")
    ep = int(mesh.shape[EXPERT_AXIS])
    if c.moe_experts <= 0:
        raise ValueError("model has no MoE layers; use the spmd/gspmd step")
    if ep > 1 and c.moe_expert_axis != EXPERT_AXIS:
        raise ValueError(f"mesh expert={ep} but model.moe_expert_axis="
                         f"{c.moe_expert_axis!r}; set it to {EXPERT_AXIS!r}")
    if c.moe_experts % max(ep, 1):
        raise ValueError(f"{c.moe_experts} experts not divisible over "
                         f"expert axis of size {ep}")
    use_seq = _seq_active(mesh, seq_axis)
    from .sequence import SEQ_SHARDED_IMPLS

    if use_seq and c.attention not in SEQ_SHARDED_IMPLS:
        raise ValueError(f"seq axis active but model attention="
                         f"{c.attention!r} is not seq-sharded")
    token_axes, expert_axes = _moe_token_axes(mesh, seq_axis)
    base = losses_lib.get(loss_name)

    def local_fwd(params, batch):
        logits, aux = model.apply(params, batch["x"], return_aux=True)
        s, cnt = base(logits, batch["y"], batch.get("mask"))
        return s, (cnt, aux)

    def micro_grads(params, batch):
        def scalar(p):
            s, (cnt, aux) = local_fwd(p, batch)
            # aux is a per-shard mean-style scalar: average it over shards,
            # weight it, and add to the per-shard loss-sum scaled by the
            # local count so the global-mean task loss + aux_weight * mean
            # aux comes out of the same psum
            return s + aux_weight * aux * cnt, (s, cnt, aux)

        (_, (s, cnt, aux)), grads = jax.value_and_grad(
            scalar, has_aux=True)(params)
        return s, cnt, aux, grads

    def shard_step(state: TrainState, batch: Batch):
        s, cnt, aux, grads = _moe_accumulate(micro_grads, state.params,
                                             batch, accum_steps)
        total = lax.psum(cnt, token_axes)
        grads = _moe_grad_psum(grads, total, token_axes, expert_axes)
        metrics = {"loss": lax.psum(s, token_axes) / total,
                   "aux": lax.pmean(aux, token_axes)}
        if grad_clip > 0:
            grads = _global_norm_clip(
                grads, grad_clip,
                lambda path: (EXPERT_AXIS,) if _is_expert_path(path) else ())
        new_params, new_opt = optimizer.update(grads, state.opt_state,
                                               state.params)
        return TrainState(state.step + 1, new_params, new_opt), metrics

    dummy = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    state_specs = moe_state_specs(optimizer, dummy)
    batch_specs = _moe_batch_specs(batch_keys, TOKEN_AXES,
                                   seq_axis if use_seq else None)
    mapped = jax.shard_map(
        shard_step, mesh=mesh,
        in_specs=(state_specs, batch_specs),
        out_specs=(state_specs, P()),
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


def make_moe_eval_step(model: Transformer, mesh: Mesh,
                       loss_name: str = "cross_entropy",
                       with_accuracy: bool = True,
                       batch_keys: Tuple[str, ...] = ("x", "y", "mask"),
                       seq_axis=None):
    """Jitted global-mean eval mirroring the train step's layout:
    (params, batch) -> metrics.  Tokens reduce over all TOKEN_AXES (the
    expert axis carries batch rows too), plus ``seq_axis`` when active;
    example-level accuracy averages the per-shard token accuracies over
    the seq axis (each shard scores its own tokens — same convention as
    the sp_tp eval)."""
    use_seq = _seq_active(mesh, seq_axis)
    token_axes = TOKEN_AXES + ((seq_axis,) if use_seq else ())
    base = losses_lib.get(loss_name)

    def shard_eval(params, batch):
        logits, _aux = model.apply(params, batch["x"], return_aux=True)
        s, c = base(logits, batch["y"], batch.get("mask"))
        total = lax.psum(c, token_axes)
        out = {"loss": lax.psum(s, token_axes) / total, "count": total}
        if with_accuracy:
            hs, hc = losses_lib.accuracy(logits, batch["y"],
                                         batch.get("mask"))
            ex_total = lax.psum(hc, TOKEN_AXES)
            acc = lax.psum(hs, TOKEN_AXES) / ex_total
            if use_seq:
                acc = lax.pmean(acc, seq_axis)
            out["accuracy"] = acc
            out["example_count"] = ex_total
        return out

    dummy = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    pspecs = moe_param_specs(dummy)
    batch_specs = _moe_batch_specs(batch_keys, TOKEN_AXES,
                                   seq_axis if use_seq else None)
    mapped = jax.shard_map(
        shard_eval, mesh=mesh,
        in_specs=(pspecs, batch_specs),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(mapped)


# ---------------------------------------------------------------------------
# DP x EP x TP: Megatron attention + tensor-sharded experts (GShard's
# expert + model parallelism) in one shard_map
# ---------------------------------------------------------------------------

TENSOR_AXIS = "tensor"

# THE single consult point for which expert-FFN leaves carry a
# tensor-sharded dim under EP x TP (each expert's hidden dim f): w_in
# (E, d, f) column-parallel, b_in (E, f) with it, w_out (E, f, d)
# row-parallel.  b_out (E, d) is expert-sharded only — it adds after the
# row-parallel psum.  Consulted by moe_tp_param_specs, the EP x TP clip
# axes, and parallel.pipeline's PP x EP x TP specs/clip, so the four
# sites cannot desynchronize (same role megatron.is_tensor_sharded plays
# for the attention/dense-FFN leaves).
TENSOR_SHARDED_EXPERT_LEAVES = ("w_in", "b_in", "w_gate", "b_gate",
                                "w_out")  # w_gate/b_gate: SwiGLU experts


def expert_leaf_tensor_spec(leaf_name: str, ndim: int,
                            tensor_axis: str = "tensor"):
    """PartitionSpec of ONE expert-FFN leaf's tensor dims, with everything
    left of the trailing layout dims (expert/scan/pipe stacks) unsharded
    — the single place the hidden-dim f placement is written down.
    Returns None for leaves with no tensor-sharded dim (b_out, gate).
    Consumed by moe_tp_param_specs (expert axis added by the caller),
    spmd.sp_tp_param_specs (experts whole; decode placement), and
    parallel.pipeline's PP x EP x TP specs."""
    if leaf_name not in TENSOR_SHARDED_EXPERT_LEAVES:
        return None
    if leaf_name == "w_out":  # (..., f, d): row-parallel on f
        return P(*(None,) * (ndim - 2), tensor_axis, None)
    # w_in (..., d, f) / b_in (..., f): column-parallel on f (last dim)
    return P(*(None,) * (ndim - 1), tensor_axis)


def moe_ffn_fn(cfg, expert_axis=None, tensor_axis=None):
    """The shared MoE-FFN block injection for ``megatron.tp_block_apply``:
    build the MoEFFN exactly once from the model config (the EP x TP
    forward and the PP x EP x TP pipeline stage body both consume this,
    so the two paths cannot drift) and return
    ``ffn_fn(layer_params, h) -> (ff, aux)``."""
    from ..models.moe import MoEFFN

    ffn = MoEFFN(
        cfg.d_model, cfg.d_ff, cfg.moe_experts,
        capacity_factor=cfg.moe_capacity_factor, capacity=cfg.moe_capacity,
        activation=cfg.activation, expert_axis=expert_axis,
        tensor_axis=tensor_axis, router_top_k=cfg.moe_top_k,
        param_dtype=cfg.param_dtype, compute_dtype=cfg.compute_dtype)
    return lambda layer_params, h: ffn.apply(layer_params["moe"], h)


def moe_tp_param_specs(params: Pytree) -> Pytree:
    """shard_map PartitionSpecs for the transformer-with-MoE param tree on a
    data x expert x tensor mesh:

    * expert FFN weights: sharded over 'expert' (leading E dim) AND
      Megatron-sharded over 'tensor' on the hidden dim f — ``w_in``
      (E, d, f) column-parallel, ``b_in`` (E, f) with it, ``w_out``
      (E, f, d) row-parallel; ``b_out`` (E, d) expert-sharded only (it adds
      after the row-parallel psum).
    * attention qkv/attn_out: the Megatron column/row layout
      (megatron.is_tensor_sharded), replicated over 'expert'.
    * gate, layernorms, embed/pos/ln_f/head: fully replicated.
    """
    from . import megatron

    def spec(path, leaf):
        names = megatron.path_names(path)
        if _is_expert_path(path):
            leaf_name = names[-1]
            ndim = len(jnp.shape(leaf))
            tspec = expert_leaf_tensor_spec(leaf_name, ndim, TENSOR_AXIS)
            if tspec is not None:
                # leading E dim additionally shards over 'expert'
                return P(EXPERT_AXIS, *tuple(tspec)[1:])
            if leaf_name == "b_out":
                return P(EXPERT_AXIS)
            raise ValueError(f"unexpected expert leaf {names}")
        if megatron.is_tensor_sharded(names):
            col = "qkv" in names or "ff_in" in names
            ndim = len(jnp.shape(leaf))
            if names[-1] == "w" and ndim == 2:
                return (P(None, TENSOR_AXIS) if col
                        else P(TENSOR_AXIS, None))
            if names[-1] == "b" and ndim == 1:
                return P(TENSOR_AXIS)
            raise ValueError(f"unexpected tensor-sharded leaf {names}")
        return P()

    return jax.tree_util.tree_map_with_path(spec, params)


def moe_tp_state_specs(optimizer: Optimizer, params: Pytree) -> TrainState:
    pspecs = moe_tp_param_specs(params)
    if optimizer.state_specs is None:
        raise ValueError(f"{optimizer.name} lacks state_specs")
    return TrainState(step=P(), params=pspecs,
                      opt_state=optimizer.state_specs(pspecs, params))


def init_moe_tp_state(model: Transformer, optimizer: Optimizer,
                      key: jax.Array, tp: int) -> TrainState:
    """Dense init + the head-aligned qkv column permutation (same
    convention as the pipeline and sp_tp layouts; inverse restores the
    dense column order for checkpoints)."""
    from . import megatron

    params = model.init(key)
    if tp > 1:
        c = model.cfg
        params = dict(params)
        params["blocks"] = megatron.permute_qkv(params["blocks"], c.d_model,
                                                c.n_heads, tp,
                                                kv_heads=c.kv_heads)
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      opt_state=optimizer.init(params))


def shard_moe_tp_state(state: TrainState, mesh: Mesh,
                       optimizer: Optimizer) -> TrainState:
    specs = moe_tp_state_specs(optimizer, state.params)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), state, specs)


def _validate_moe_tp(model: Transformer, mesh: Mesh, seq_axis=None):
    from . import megatron
    from .sequence import SEQ_SHARDED_IMPLS

    c = model.cfg
    c.require_plain_block("the expert x tensor step (parallel/expert.py)")
    ep = int(mesh.shape.get(EXPERT_AXIS, 1))
    tp = int(mesh.shape.get(TENSOR_AXIS, 1))
    use_seq = _seq_active(mesh, seq_axis)
    sp = int(mesh.shape[seq_axis]) if use_seq else 1
    if tp < 2 or (ep < 2 and not use_seq):
        raise ValueError(f"the MoE x TP step needs tensor>1 and "
                         f"(expert>1 or an active seq axis); got expert="
                         f"{ep}, tensor={tp}, seq={sp} — use the plain "
                         "expert/gspmd/spmd paths otherwise")
    if c.moe_experts <= 0:
        raise ValueError("EP x TP requires a transformer with moe_experts "
                         "> 0 (--moe_experts)")
    if c.moe_experts % max(ep, 1):
        raise ValueError(f"{c.moe_experts} experts not divisible over "
                         f"expert axis of size {ep}")
    megatron.validate_tp(c, tp)
    if use_seq:
        if c.attention not in SEQ_SHARDED_IMPLS:
            raise ValueError(
                f"seq axis {seq_axis!r}={sp} is active but attention="
                f"{c.attention!r} is not seq-sharded "
                f"({SEQ_SHARDED_IMPLS})")
        if c.attention == "ulysses":
            from .sequence import validate_ulysses_under_tp

            validate_ulysses_under_tp(c.n_heads, tp, sp, seq_axis)
    elif c.attention not in ("dense", "auto"):
        # "auto" resolves to dense here: this step's only wired unsharded
        # attention is the Megatron dense path (attention_fn=None)
        raise ValueError("the EP x TP step runs Megatron attention over the "
                         f"full local sequence; attention={c.attention!r} "
                         "needs seq_axis (SP x EP x TP) or the sp/sp_ep "
                         "paths")
    if c.scan_layers:
        raise ValueError("scan_layers is a plain-DP/SP layout; the EP x TP "
                         "step owns its own per-layer loop")
    return ep, tp


def _moe_tp_forward(model: Transformer, params: Pytree, ids: jax.Array,
                    tp: int, ep: int = 2, seq_axis=None):
    """Local (SP x) EP x TP forward inside shard_map: replicated embed,
    Megatron blocks (heads over 'tensor') whose FFN is the
    expert+tensor-sharded MoEFFN (slots over 'expert' by all_to_all when
    ``ep > 1``, hidden dim over 'tensor'), replicated LN + head.  Reuses
    Transformer.embed/head_logits so the composed path cannot drift from
    the dense model.

    ``seq_axis`` composes sequence parallelism in: the sequence dim is
    sharded over that axis, positions come from the shard's global offset
    and attention runs the model's seq-sharded impl (ring/ulysses/
    striped...) over the local heads — Megatron TP x context parallelism
    x expert parallelism in one program.  With ``ep == 1`` (no expert
    axis) the experts are held whole on every shard and only their hidden
    dim is tensor-sharded — the SP x TP MoE layout."""
    from . import megatron

    c = model.cfg
    ffn_fn = moe_ffn_fn(c, expert_axis=EXPERT_AXIS if ep > 1 else None,
                        tensor_axis=TENSOR_AXIS)

    b, t = ids.shape
    if seq_axis is not None:
        from .sequence import global_positions, sequence_sharded_attention

        positions = global_positions(c.attention, seq_axis, t)
        attn = lambda q, k, v: sequence_sharded_attention(
            c.attention, q, k, v, axis=seq_axis, causal=True,
            block_q=c.flash_block_q, block_k=c.flash_block_k,
            rope_theta=(c.rope_theta if c.pos_encoding == "rope"
                        else None))
    else:
        positions = jnp.arange(t)
        attn = None
    x = model.embed(params, ids, positions)

    def block_fn(layer_params, h):
        return megatron.tp_block_apply(c, layer_params, h, tp, ffn_fn=ffn_fn,
                                       attention_fn=attn)

    if c.remat:
        from ..models.core import make_remat

        block_fn = make_remat(c.remat_policy)(block_fn)
    aux_total = jnp.zeros((), jnp.float32)
    for layer_params in params["blocks"]:
        x, aux = block_fn(layer_params, x)
        aux_total = aux_total + aux
    return model.head_logits(params, x), aux_total


def make_moe_tp_train_step(model: Transformer, optimizer: Optimizer,
                           mesh: Mesh, loss_name: str = "cross_entropy",
                           aux_weight: float = 0.01,
                           donate: bool = True,
                           batch_keys: Tuple[str, ...] = ("x", "y", "mask"),
                           grad_clip: float = 0.0,
                           accum_steps: int = 1,
                           seq_axis=None):
    """(state, batch) -> (state, metrics) jitted over data x expert x tensor
    — GShard's expert + model parallelism, TPU-native: Megatron-sharded
    attention (heads over 'tensor'), expert FFNs sharded over BOTH 'expert'
    (whole experts, all_to_all slot exchange) and 'tensor' (each expert's
    hidden dim, psum combine).  The reference has neither strategy
    (SURVEY.md §2.2); one-step parity vs the single-device dense-MoE model
    is pinned by tests/test_moe.py::test_expert_tensor_parallel_matches_dense
    and the Trainer wiring by tests/test_trainer_pp_ep.py.

    ``seq_axis`` composes sequence/context parallelism in: the model's
    attention must be a seq-sharded impl (ring/ulysses/striped...), the
    sequence dim of x/y shards over that axis, and every token reduction
    additionally spans it.  With the mesh's expert axis at 1 this is the
    SP x TP MoE layout (experts whole, hidden dim tensor-sharded, no
    all_to_all); with expert>1 it is the full SP x EP x TP composition.

    ``grad_clip`` clips by the global norm with per-leaf shard accounting:
    expert+tensor-sharded leaves psum their squared norms over
    ('expert','tensor'), expert-only leaves over ('expert',), tensor-only
    leaves over ('tensor',); replicated leaves carry full grads.
    """
    from . import megatron

    ep, tp = _validate_moe_tp(model, mesh, seq_axis)
    seq = seq_axis if _seq_active(mesh, seq_axis) else None
    token_axes, expert_axes = _moe_token_axes(mesh, seq_axis)
    base = losses_lib.get(loss_name)

    def local_fwd(params, batch):
        logits, aux = _moe_tp_forward(model, params, batch["x"], tp, ep,
                                      seq)
        s, cnt = base(logits, batch["y"], batch.get("mask"))
        return s, (cnt, aux)

    def micro_grads(params, batch):
        def scalar(p):
            s, (cnt, aux) = local_fwd(p, batch)
            return s + aux_weight * aux * cnt, (s, cnt, aux)

        (_, (s, cnt, aux)), grads = jax.value_and_grad(
            scalar, has_aux=True)(params)
        return s, cnt, aux, grads

    def clip_axes(path) -> Tuple[str, ...]:
        names = megatron.path_names(path)
        if _is_expert_path(path):
            if names[-1] in TENSOR_SHARDED_EXPERT_LEAVES:
                return (EXPERT_AXIS, TENSOR_AXIS)
            return (EXPERT_AXIS,)
        if megatron.is_tensor_sharded(names):
            return (TENSOR_AXIS,)
        return ()

    def shard_step(state: TrainState, batch: Batch):
        s, cnt, aux, grads = _moe_accumulate(micro_grads, state.params,
                                             batch, accum_steps)
        total = lax.psum(cnt, token_axes)
        grads = _moe_grad_psum(grads, total, token_axes, expert_axes)
        metrics = {"loss": lax.psum(s, token_axes) / total,
                   "aux": lax.pmean(aux, token_axes)}
        if grad_clip > 0:
            grads = _global_norm_clip(grads, grad_clip, clip_axes)
        new_params, new_opt = optimizer.update(grads, state.opt_state,
                                               state.params)
        return TrainState(state.step + 1, new_params, new_opt), metrics

    dummy = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    state_specs = moe_tp_state_specs(optimizer, dummy)
    batch_specs = _moe_batch_specs(batch_keys, TOKEN_AXES, seq)
    mapped = jax.shard_map(
        shard_step, mesh=mesh,
        in_specs=(state_specs, batch_specs),
        out_specs=(state_specs, P()),
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


def make_moe_tp_eval_step(model: Transformer, mesh: Mesh,
                          loss_name: str = "cross_entropy",
                          with_accuracy: bool = True,
                          batch_keys: Tuple[str, ...] = ("x", "y", "mask"),
                          seq_axis=None):
    """Jitted global-mean eval on the (SP x) EP x TP layout, params
    consumed in place: (params, batch) -> metrics.  With an active
    ``seq_axis``, token reductions span it and example-level accuracy
    averages the per-shard token accuracies over the seq axis (same
    convention as the sp_tp/moe eval steps)."""
    ep, tp = _validate_moe_tp(model, mesh, seq_axis)
    seq = seq_axis if _seq_active(mesh, seq_axis) else None
    token_axes, _ = _moe_token_axes(mesh, seq_axis)
    base = losses_lib.get(loss_name)

    def shard_eval(params, batch):
        logits, _aux = _moe_tp_forward(model, params, batch["x"], tp, ep,
                                       seq)
        s, c = base(logits, batch["y"], batch.get("mask"))
        total = lax.psum(c, token_axes)
        out = {"loss": lax.psum(s, token_axes) / total, "count": total}
        if with_accuracy:
            hs, hc = losses_lib.accuracy(logits, batch["y"],
                                         batch.get("mask"))
            ex_total = lax.psum(hc, TOKEN_AXES)
            acc = lax.psum(hs, TOKEN_AXES) / ex_total
            if seq:
                acc = lax.pmean(acc, seq)
            out["accuracy"] = acc
            out["example_count"] = ex_total
        return out

    dummy = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    pspecs = moe_tp_param_specs(dummy)
    batch_specs = _moe_batch_specs(batch_keys, TOKEN_AXES, seq)
    mapped = jax.shard_map(
        shard_eval, mesh=mesh,
        in_specs=(pspecs, batch_specs),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(mapped)


def run_one_step(model: Transformer, optimizer: Optimizer, mesh: Mesh,
                 batch: Batch, key: jax.Array,
                 loss_name: str = "cross_entropy",
                 aux_weight: float = 0.01
                 ) -> Tuple[TrainState, Dict[str, jax.Array]]:
    """Convenience for dry-runs and tests: init, place, one MoE step."""
    state = TrainState.create(model, optimizer, key)
    state = shard_moe_state(state, mesh, optimizer)
    placed = {k: jax.device_put(jnp.asarray(v),
                                NamedSharding(mesh, P(TOKEN_AXES)))
              for k, v in batch.items()}
    step = make_moe_train_step(model, optimizer, mesh, loss_name, aux_weight,
                               donate=False, batch_keys=tuple(placed))
    return step(state, placed)
