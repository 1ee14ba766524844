"""Automatic per-leaf cross-replica weight-update sharding.

Generalizes the hand-rolled zero1 flat-buffer path
(``data_parallel.zero1_*``) into a layout-agnostic layer per "Automatic
Cross-Replica Sharding of Weight Update in Data-Parallel Training"
(arXiv 2004.13336) and the compiler-driven reduce-scatter/all-gather
formulation of "Scalable Training of Language Models using JAX pjit and
TPUv4" (arXiv 2204.06514):

* **Plan** (:func:`plan_updates`): for every parameter leaf, shard the
  weight update along the leaf's LARGEST dimension across the data axes,
  padding that dimension to a multiple of the data-axis size; leaves
  smaller than ``min_shard_elems`` fall back to a replicated update (the
  padding + collective latency would outweigh the 1/N win there).  The
  rule is deliberately independent of the data-axis size N, so a
  checkpoint written by an N-replica world re-pads onto M replicas
  without re-deriving which leaves are sharded (utils.checkpoint).
* **shard_map paths** (:func:`sharded_update`, used by the DP and DP x SP
  step builders): per-leaf ``psum_scatter`` of the gradient (a fused
  reduce-scatter instead of a full psum) -> shard-local optimizer update
  on the 1/N parameter slice with the 1/N optimizer state ->
  ``all_gather`` of the updated slices.  Each leaf's reduce-scatter
  depends only on that leaf's gradient, so XLA schedules it against the
  remaining backward compute (comm/compute overlap —
  :func:`collective_report` extracts the evidence from the compiled HLO).
* **GSPMD path** (:func:`gspmd_opt_specs`): the same sharding expressed
  as explicit opt-state ``NamedSharding``s — the partitioner then
  materializes the reduce-scatter/all-gather pair itself and schedules it
  against the backward pass.
* **Mixed precision** (``ops.optim.with_master_weights``): bf16
  param/grad storage with the f32 master copy living ONLY in the sharded
  optimizer state — master memory is 1/N per replica (the 2004.13336
  trick), and the param all-gather moves half the bytes.

Same math as the replicated update (global-mean gradient, global-norm
clip from psum'd shard norms, skip-guard predicate on the psum'd global
norm so the decision is identical on every replica); optimizer-state
memory and update FLOPs drop by the data-axis size.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.optim import Optimizer
from ..train.state import TrainState
from .data_parallel import DATA_AXES, data_axis_size

Pytree = Any

# leaves below this many elements keep the replicated update: the per-leaf
# reduce-scatter/all-gather latency and the padding waste outweigh a 1/N
# saving that is already negligible (biases, LN scales, scalar counts).
# Deliberately N-independent — see plan_updates.
DEFAULT_MIN_SHARD_ELEMS = 1024


@dataclasses.dataclass(frozen=True)
class LeafPlan:
    """How one parameter leaf's update is sharded.

    ``axis=None`` = replicated update (tiny leaf).  Otherwise the leaf's
    dimension ``axis`` is padded to ``padded`` (a multiple of the
    data-axis size) and scattered; each replica owns a ``shard``-long
    slice of it.
    """

    axis: Optional[int]
    padded: int = 0
    shard: int = 0


def _is_plan(x) -> bool:
    return isinstance(x, LeafPlan)


def plan_updates(params: Pytree, n: int,
                 min_shard_elems: int = DEFAULT_MIN_SHARD_ELEMS) -> Pytree:
    """Per-leaf :class:`LeafPlan` tree (largest-dimension scatter with
    padding; replicated fallback for tiny leaves).

    The shard-or-replicate decision and the axis choice depend only on
    the leaf SHAPE (never on ``n``), so two worlds of different size
    derive the same plan for the same model — the property the
    checkpoint N->M reshard relies on (only padding differs).  Works on
    concrete arrays, ``ShapeDtypeStruct``s and tracers alike.
    """

    def one(leaf) -> LeafPlan:
        shape = tuple(jnp.shape(leaf))
        size = int(np.prod(shape)) if shape else 1
        if n <= 1 or not shape or size < min_shard_elems:
            return LeafPlan(None)
        axis = int(np.argmax(shape))
        padded = -(-shape[axis] // n) * n
        return LeafPlan(axis, padded, padded // n)

    return jax.tree_util.tree_map(one, params)


def pad_leaf(x, plan: LeafPlan):
    """Zero-pad the planned dimension up to ``plan.padded`` (identity for
    replicated leaves and already-padded shapes)."""
    if plan.axis is None:
        return x
    pad = plan.padded - x.shape[plan.axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[plan.axis] = (0, pad)
    return jnp.pad(x, widths)


def opt_param_specs(plan: Pytree,
                    axes: Tuple[str, ...] = DATA_AXES) -> Pytree:
    """PartitionSpec tree mirroring the plan: the planned dimension over
    the data axes, everything else (and replicated leaves) unsharded —
    the spec tree :func:`state_spec`/placement hand to
    ``Optimizer.state_specs`` so every mirror-layout slot (momentum, mu,
    nu, the master copy) inherits the leaf's update sharding."""

    def one(p: LeafPlan) -> P:
        if p.axis is None:
            return P()
        return P(*((None,) * p.axis), axes)

    return jax.tree_util.tree_map(one, plan, is_leaf=_is_plan)


def init_opt_state(optimizer: Optimizer, params: Pytree,
                   plan: Pytree) -> Pytree:
    """Host-side optimizer state for the sharded update: the optimizer is
    initialized on the PADDED param tree, so every mirror-layout slot
    (and ``with_master_weights``'s f32 master copy) carries the padded
    shapes the scattered update slices.  Padding regions hold zeros and
    stay zero (their gradients are zero by construction).

    Slots are initialized in f32 regardless of the param storage dtype
    (the same contract as zero1's flat f32 buffer): the update consumes
    the f32 reduce-scattered gradient, so bf16-initialized slots would
    silently promote to f32 on the first step — a dtype flip that breaks
    in/out buffer aliasing (donation) and the checkpoint resume
    template.  f32 slots are also simply correct mixed precision:
    momentum in the storage dtype is where bf16 training loses its
    update signal."""
    padded = jax.tree_util.tree_map(
        lambda x, p: pad_leaf(x, p).astype(jnp.float32)
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
        else pad_leaf(x, p),
        params, plan)
    return optimizer.init(padded)


def state_spec(optimizer: Optimizer, plan: Pytree) -> TrainState:
    """shard_map in/out spec for a sharded-update TrainState: step and
    params replicated, optimizer state per-leaf scattered."""
    if optimizer.state_specs is None:
        raise ValueError(f"{optimizer.name} lacks state_specs")
    return TrainState(step=P(), params=P(),
                      opt_state=optimizer.state_specs(opt_param_specs(plan)))


def place_state(state: TrainState, mesh: Mesh, optimizer: Optimizer,
                plan: Pytree) -> TrainState:
    """Place a host TrainState in the sharded-update layout: step/params
    replicated, opt-state leaves scattered per the plan (fresh init and
    checkpoint resume both land here)."""
    if optimizer.state_specs is None:
        raise ValueError(f"{optimizer.name} lacks state_specs")
    opt_spec = optimizer.state_specs(opt_param_specs(plan))
    rep = NamedSharding(mesh, P())
    return TrainState(
        step=jax.device_put(state.step, rep),
        params=jax.device_put(state.params, rep),
        opt_state=jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            state.opt_state, opt_spec),
        qstate=jax.device_put(state.qstate, rep))


def _grad_sq(leaves) -> jax.Array:
    sq = jnp.zeros((), jnp.float32)
    for g in leaves:
        sq = sq + jnp.sum(jnp.square(g.astype(jnp.float32)))
    return sq


def sharded_update(optimizer: Optimizer, state: TrainState, s, c, grads,
                   mesh: Mesh, plan: Pytree, grad_clip: float = 0.0,
                   extra_reduce_axes: Tuple[str, ...] = (),
                   with_metrics: bool = False):
    """The per-leaf sharded weight update (call inside ``shard_map``;
    shared by the DP and DP x SP step builders).

    Per sharded leaf: reduce-scatter the gradient along its planned
    dimension over the data axes, update the local 1/N parameter slice
    with the local 1/N optimizer state, all-gather the updated slices.
    Replicated-plan leaves take the ordinary full psum + full update.

    ``grad_clip > 0`` clips by the GLOBAL norm: replicated-leaf squares
    are identical everywhere, scattered-leaf squares psum over the data
    axes — one extra scalar psum, never a shard-local clip.  The same
    psum'd norm feeds ``Optimizer.update_with_norm`` when the optimizer
    carries one (the skip guard), so the skip decision is identical on
    every replica, and the telemetry metrics vector when
    ``with_metrics`` — grad norm from the scattered shards via that one
    psum, param/update norms from the gathered full tree (local math,
    identical on every replica).  The update expressions are unchanged by
    ``with_metrics``, so params stay bitwise-equal with metrics on vs
    off.

    ``extra_reduce_axes`` (e.g. ``('seq',)``): loss terms and
    replicated-leaf grads reduce over them too; scattered shards are
    psum'd over them after the data-axis reduce-scatter (the reductions
    commute).
    """
    reduce_axes = DATA_AXES + tuple(extra_reduce_axes)
    p_leaves, treedef = jax.tree_util.tree_flatten(state.params)
    g_leaves = jax.tree_util.tree_leaves(grads)
    plans = jax.tree_util.tree_leaves(plan, is_leaf=_is_plan)
    assert len(p_leaves) == len(g_leaves) == len(plans), (
        "update plan does not mirror the param tree")

    # ``grad_exchange`` names the work (gradients in, this replica's
    # updated parameters everywhere), whatever collectives implement it:
    # the reduce-scatter here and the parameter all-gather below
    with jax.named_scope("grad_exchange"):
        total = lax.psum(c, reduce_axes)
        loss = lax.psum(s, reduce_axes) / total
        idx = lax.axis_index(DATA_AXES)
        g_mixed, p_mixed = [], []
        for p, g, pl in zip(p_leaves, g_leaves, plans):
            g32 = g.astype(jnp.float32)
            if pl.axis is None:
                gr = lax.psum(g32, reduce_axes) / total
                g_mixed.append(gr)
                p_mixed.append(p)
                continue
            gs = lax.psum_scatter(pad_leaf(g32, pl), DATA_AXES,
                                  scatter_dimension=pl.axis, tiled=True)
            if extra_reduce_axes:
                gs = lax.psum(gs, tuple(extra_reduce_axes))
            g_mixed.append(gs / total)
            pp = pad_leaf(p, pl)
            start = [0] * p.ndim
            start[pl.axis] = idx * pl.shard
            sizes = list(pp.shape)
            sizes[pl.axis] = pl.shard
            p_mixed.append(lax.dynamic_slice(pp, tuple(start),
                                             tuple(sizes)))

        # one global grad norm (pre-clip, matching the replicated path
        # where the guard measures before optim.with_clipping):
        # replicated-leaf squares are already identical on every replica;
        # scattered-leaf partial squares need one scalar psum (padding
        # lanes are zero)
        gnorm = None
        if (grad_clip > 0 or with_metrics
                or optimizer.update_with_norm is not None):
            sq_rep = _grad_sq(g for g, pl in zip(g_mixed, plans)
                              if pl.axis is None)
            sq_sh = _grad_sq(g for g, pl in zip(g_mixed, plans)
                             if pl.axis is not None)
            gnorm = jnp.sqrt(sq_rep + lax.psum(sq_sh, DATA_AXES))
        if grad_clip > 0:
            scale = jnp.minimum(1.0, grad_clip / jnp.maximum(gnorm, 1e-12))
            g_mixed = [g * scale for g in g_mixed]

    g_tree = jax.tree_util.tree_unflatten(treedef, g_mixed)
    p_tree = jax.tree_util.tree_unflatten(treedef, p_mixed)
    with jax.named_scope("optimizer_update"):
        if optimizer.update_with_norm is not None:
            new_p_mixed, new_opt = optimizer.update_with_norm(
                g_tree, state.opt_state, p_tree, gnorm)
        else:
            new_p_mixed, new_opt = optimizer.update(g_tree, state.opt_state,
                                                    p_tree)

    new_full = []
    with jax.named_scope("grad_exchange"):
        for np_, p, pl in zip(jax.tree_util.tree_leaves(new_p_mixed),
                              p_leaves, plans):
            if pl.axis is None:
                new_full.append(np_)
                continue
            gathered = lax.all_gather(np_, DATA_AXES, axis=pl.axis,
                                      tiled=True)
            if gathered.shape[pl.axis] != p.shape[pl.axis]:
                gathered = lax.slice_in_dim(gathered, 0, p.shape[pl.axis],
                                            axis=pl.axis)
            new_full.append(gathered)
    new_params = jax.tree_util.tree_unflatten(treedef, new_full)
    new_state = TrainState(state.step + 1, new_params, new_opt)
    if not with_metrics:
        return new_state, loss
    from ..train import telemetry

    return new_state, telemetry.metrics_vector(
        loss, gnorm, new_params, state.params, new_opt)


# ---------------------------------------------------------------------------
# GSPMD: the same sharding as explicit opt-state NamedShardings
# ---------------------------------------------------------------------------

def gspmd_opt_specs(pspecs: Pytree, params: Pytree, mesh: Mesh,
                    min_shard_elems: int = DEFAULT_MIN_SHARD_ELEMS
                    ) -> Pytree:
    """Param-spec tree for the GSPMD path's OPTIMIZER STATE under
    ``update_sharding='sharded'``: each leaf's largest dimension that is
    (a) not already consumed by a TP/FSDP axis and (b) divisible by the
    'data' axis size additionally carries ``'data'``.  Handing the result
    to ``Optimizer.state_specs`` shards every mirror slot (and the master
    copy) over the data axis while the PARAMS keep their original specs —
    the jit in/out shardings then make XLA materialize the
    reduce-scatter(grads)/all-gather(params) pair itself and schedule it
    against the backward pass (the arXiv 2204.06514 formulation).

    GSPMD shards concrete (unpadded) dims, so non-divisible dims fall to
    the next-largest candidate rather than padding; a leaf with no
    candidate keeps its param sharding (replicated update there).
    """
    data = int(mesh.shape.get("data", 1))
    if data <= 1:
        return pspecs

    def one(spec: P, p) -> P:
        shape = tuple(jnp.shape(p))
        size = int(np.prod(shape)) if shape else 1
        if not shape or size < min_shard_elems:
            return spec
        entries = tuple(spec) + (None,) * (len(shape) - len(tuple(spec)))
        cands = [d for d in range(len(shape))
                 if entries[d] is None and shape[d] % data == 0
                 and shape[d] >= data]
        if not cands:
            return spec
        d = max(cands, key=lambda i: shape[i])
        new = list(entries)
        new[d] = "data"
        return P(*new)

    return jax.tree_util.tree_map(one, pspecs, params,
                                  is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Compiled-HLO evidence: collectives + comm/compute overlap, and donation
# ---------------------------------------------------------------------------

# matches the sync forms (XLA:CPU) AND the async `-start` halves (TPU
# emits reduce-scatter-start/-done pairs); `-done` deliberately excluded
# so async collectives count once
_COLLECTIVE_RE = re.compile(
    r"=\s+\S+\s+(reduce-scatter|all-gather|all-reduce)(?:-start)?\(")
_DOT_RE = re.compile(r"=\s+\S+\s+dot\(")


def collective_report(hlo_text: str) -> Dict[str, Any]:
    """Parse a compiled step's HLO text into the overlap-evidence record
    (bench --update-sharding-ab and the regression tests consume this).

    * ``counts``: reduce-scatter / all-gather / all-reduce instruction
      counts.  The sharded step's signature is many per-leaf
      reduce-scatters and NO param-sized all-reduce; the replicated
      step's is the inverse.
    * ``dots_after_first_reduce_scatter``: backward/forward matmuls that
      appear after the first reduce-scatter in the (topologically
      ordered) instruction stream.  > 0 means the reduce-scatters are
      NOT serialized behind the whole backward pass — each depends only
      on its own leaf's gradient, so the scheduler is free to overlap
      them with the remaining compute.
    """
    seq = []
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if m:
            seq.append(m.group(1))
            continue
        if _DOT_RE.search(line):
            seq.append("dot")
    counts = {k: seq.count(k)
              for k in ("reduce-scatter", "all-gather", "all-reduce")}
    dots = [i for i, k in enumerate(seq) if k == "dot"]
    rs = [i for i, k in enumerate(seq) if k == "reduce-scatter"]
    after = sum(1 for d in dots if rs and d > rs[0])
    return {
        "counts": counts,
        "n_dots": len(dots),
        "dots_after_first_reduce_scatter": after,
        "overlap_schedulable": bool(rs and after > 0),
    }
