"""Mixture-of-Experts feed-forward layer (Switch-style top-1 routing).

The reference has no MoE (SURVEY.md §2.2: expert parallelism "not required")
— this is a TPU-native capability layered on top of parity, built the way
MoE maps onto XLA rather than onto per-process MPI alltoallv:

* **Static shapes everywhere.**  Routing is expressed as dense one-hot
  dispatch/combine tensors with a fixed per-expert capacity ``C`` — the
  einsum formulation of GShard/Switch — so XLA sees only matmuls, never
  data-dependent gather sizes.  Tokens overflowing an expert's capacity are
  dropped (contribute zero), the standard trade.
* **Expert parallelism is one pair of `lax.all_to_all`s.**  With experts
  sharded over the mesh's 'expert' axis, the locally-dispatched slot tensor
  ``(E, C, d)`` is exchanged so each device receives every peer's slots for
  its own experts, runs its expert FFNs as one batched einsum on the MXU,
  and the reverse all_to_all brings results home (parallel.expert wires the
  train step).
* **Load balancing** is the Switch aux loss ``E * Σ_e f_e · p_e`` (fraction
  of tokens routed to e times mean router prob for e), returned alongside
  the output for the trainer to weight.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .core import ACTIVATIONS, Module, Pytree, _uniform


# ---- the router, shared by the capacity layer and the layer without drops --

def router_probs(gate_params: Pytree, x: jax.Array) -> jax.Array:
    """(N, d) -> (N, E) softmax scores over ALL of the layer's experts, in
    float32 whatever the compute type: the k-th and (k+1)-th score are often
    closer than a bfloat16 step, and a flipped choice is another expert."""
    logits = jnp.matmul(x.astype(jnp.float32),
                        gate_params["w"].astype(jnp.float32))
    return jax.nn.softmax(logits, axis=-1)


def top_k_weights(probs: jax.Array, k: int):
    """The k largest scores of each token, divided by their sum
    (GShard's renormalisation, ``norm_topk_prob``): ((N, k) weights,
    (N, k) expert ids, best first)."""
    top_p, top_i = jax.lax.top_k(probs, k)
    return top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9), top_i


def sigmoid_top_k(gate_params: Pytree, x: jax.Array, k: int):
    """The sigmoid router (DeepSeek-V3's): scores ``sigmoid(x W)`` in
    float32 over ALL experts; the k chosen are the k largest of ``score +
    bias`` (``gate_params["bias"]``, the stored correction bias), and their
    weights are the chosen SCORES divided by their sum, so the bias takes
    part in the choice and not in the weight.  ((N, k) weights, (N, k)
    expert ids, (N, E) scores)."""
    scores = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), gate_params["w"].astype(jnp.float32)))
    _, top_i = jax.lax.top_k(
        scores + gate_params["bias"].astype(jnp.float32), k)
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    return top_s / (top_s.sum(-1, keepdims=True) + 1e-20), top_i, scores


@dataclass(frozen=True)
class MoEFFN(Module):
    """Top-1 gated mixture of ``n_experts`` two-layer FFNs.

    ``expert_axis`` selects the execution path:
    * ``None`` — dense: every device holds all experts (or there is one
      device); pure einsum, no collectives.
    * an axis name — expert-parallel: expert params are sharded over that
      mesh axis (leading expert dim), and apply() must run inside a
      ``shard_map`` that binds the axis; slots travel by all_to_all.

    ``tensor_axis`` additionally Megatron-shards every expert's FFN over
    that mesh axis: the local ``w_in``/``b_in`` hold a column slice
    (E_local, d, f/tp) of the hidden units, ``w_out`` the matching row
    slice (E_local, f/tp, d), and the row-parallel output is psum'd over
    the axis before ``b_out`` (replicated) is added — GShard's
    expert + model parallelism.  Activations entering apply() must be
    replicated over ``tensor_axis`` (parallel.expert's EP x TP step wires
    the f/g conjugate ops so the backward collective is explicit).

    ``capacity`` is the per-routing-group per-expert slot count; default
    ``ceil(capacity_factor * group_tokens / n_experts)``.
    """

    d_model: int
    d_ff: int
    n_experts: int
    capacity_factor: float = 1.25
    capacity: Optional[int] = None
    activation: str = "gelu"
    expert_axis: Optional[str] = None
    tensor_axis: Optional[str] = None
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    # 1 = Switch top-1 (combine weight = the chosen expert's raw prob);
    # k>1 = GShard-style top-k (weights = the top-k probs renormalized,
    # rank-0 choices claim expert queue slots before rank-1, etc.)
    router_top_k: int = 1

    def init(self, key: jax.Array) -> Pytree:
        kg, k1, k2, k3, k4, k5, k6 = jax.random.split(key, 7)
        e, d, f = self.n_experts, self.d_model, self.d_ff
        bd, bf = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
        experts = {
            "w_in": _uniform(k1, (e, d, f), bd, self.param_dtype),
            "b_in": _uniform(k2, (e, f), bd, self.param_dtype),
            "w_out": _uniform(k3, (e, f, d), bf, self.param_dtype),
            "b_out": _uniform(k4, (e, d), bf, self.param_dtype),
        }
        if self.activation == "swiglu":
            # gated experts (round 4): silu(x W_gate) * (x W_in) per
            # expert — same column layout as w_in/b_in, so the tensor-
            # sharding spec and the EP dispatch treat it identically
            experts["w_gate"] = _uniform(k5, (e, d, f), bd,
                                         self.param_dtype)
            experts["b_gate"] = _uniform(k6, (e, f), bd, self.param_dtype)
        return {
            "gate": {"w": _uniform(kg, (d, e), bd, self.param_dtype)},
            "experts": experts,
        }

    # ---- routing -------------------------------------------------------

    def __post_init__(self):
        if not 1 <= self.router_top_k <= self.n_experts:
            raise ValueError(
                f"router_top_k must be in [1, n_experts={self.n_experts}], "
                f"got {self.router_top_k}")

    def _capacity(self, n_tokens: int) -> int:
        if self.capacity is not None:
            return self.capacity
        # top-k demand is k assignments per token (GShard scales capacity
        # by k; without this, default top-2 would drop >= 37% of
        # assignments even under perfectly uniform load)
        return max(1, math.ceil(self.capacity_factor * self.router_top_k
                                * n_tokens / self.n_experts))

    @staticmethod
    def _assign_slots(onehot: jax.Array, cap: int, counts: jax.Array):
        """Queue positions for one choice rank: each token's 0-based slot in
        its expert's queue, offset by ``counts`` (slots already claimed by
        earlier ranks).  Returns ((N, E, C) dispatch mask, updated counts)."""
        pos = (jnp.cumsum(onehot, axis=0) - 1.0
               + counts[None, :]) * onehot               # (N, E)
        pos_tok = pos.sum(-1)                            # (N,)
        keep = (pos_tok < cap) & (onehot.sum(-1) > 0)
        slot = jax.nn.one_hot(pos_tok.astype(jnp.int32), cap,
                              dtype=jnp.float32)         # (N, C)
        mask = (onehot[:, :, None] * slot[:, None, :]
                * keep[:, None, None].astype(jnp.float32))
        return mask, counts + onehot.sum(0)

    def _route(self, gate_params: Pytree, x: jax.Array, cap: int):
        """x: (N, d) -> dispatch (N, E, C) bool-ish, combine (N, E, C),
        aux scalar."""
        e, k = self.n_experts, self.router_top_k
        probs = router_probs(gate_params, x)               # (N, E)
        counts = jnp.zeros((e,), jnp.float32)
        if k == 1:
            # Switch: combine weight = the chosen expert's RAW probability
            onehot = jax.nn.one_hot(jnp.argmax(probs, axis=-1), e,
                                    dtype=jnp.float32)
            gate_val = (probs * onehot).sum(-1)            # (N,)
            dispatch, _ = self._assign_slots(onehot, cap, counts)
            combine = dispatch * gate_val[:, None, None]
            top1 = onehot
        else:
            # GShard-style top-k: weights are the top-k probs renormalized;
            # rank r claims expert queue slots after ranks < r (dropped
            # tokens still consume their attempted position — keeps slot
            # assignment one cumsum per rank instead of data-dependent)
            weights, top_i = top_k_weights(probs, k)       # (N, k)
            dispatch = jnp.zeros((x.shape[0], e, cap), jnp.float32)
            combine = jnp.zeros_like(dispatch)
            for r in range(k):
                onehot = jax.nn.one_hot(top_i[:, r], e, dtype=jnp.float32)
                mask, counts = self._assign_slots(onehot, cap, counts)
                dispatch = dispatch + mask
                combine = combine + mask * weights[:, r][:, None, None]
                if r == 0:
                    top1 = onehot
        # load-balance loss on the primary assignment (Switch / GShard
        # convention): E * sum_e f_e * p_e  (1.0 when uniform)
        f_e = top1.mean(0)
        p_e = probs.mean(0)
        aux = e * jnp.sum(f_e * p_e)
        return dispatch, combine, aux

    # ---- expert compute ------------------------------------------------

    def _experts_ffn(self, ep: Pytree, slots: jax.Array) -> jax.Array:
        """slots: (E_local, S, d) -> (E_local, S, d); one batched einsum
        pair per layer — E_local independent matmuls tiled onto the MXU.

        With ``tensor_axis``, the local ``w_in``/``b_in``/``w_out`` hold
        Megatron column/row shards (hidden dim f/tp) and the row-parallel
        partial output is psum'd over the axis before the replicated
        ``b_out``; the f operator at entry makes the backward psum of the
        input-cotangents explicit (megatron.make_megatron_ops)."""
        cdt = self.compute_dtype
        if self.tensor_axis is not None:
            from ..parallel.megatron import make_megatron_ops

            f, g = make_megatron_ops(self.tensor_axis)
            slots = f(slots)
        h = jnp.einsum("esd,edf->esf", slots.astype(cdt),
                       ep["w_in"].astype(cdt))
        if "w_in_scale" in ep:
            # weights-only int8 experts (ops.quant): per-(expert, column)
            # scale folded into the einsum output BEFORE bias/activation
            h = h * ep["w_in_scale"][:, None, :].astype(cdt)
        h = h + ep["b_in"][:, None, :].astype(cdt)
        if self.activation == "swiglu":
            # gated experts: the gate shares w_in's column layout, so
            # under tensor sharding the local gated product is the local
            # shard of the global one (same argument as the dense TP FFN)
            gate = jnp.einsum("esd,edf->esf", slots.astype(cdt),
                              ep["w_gate"].astype(cdt))
            if "w_gate_scale" in ep:
                gate = gate * ep["w_gate_scale"][:, None, :].astype(cdt)
            gate = gate + ep["b_gate"][:, None, :].astype(cdt)
            h = jax.nn.silu(gate) * h
        else:
            h = ACTIVATIONS[self.activation](h)
        out = jnp.einsum("esf,efd->esd", h, ep["w_out"].astype(cdt))
        if "w_out_scale" in ep:
            out = out * ep["w_out_scale"][:, None, :].astype(cdt)
        if self.tensor_axis is not None:
            out = g(out)
        return out + ep["b_out"][:, None, :].astype(cdt)

    def apply(self, params: Pytree, x: jax.Array, **kwargs
              ) -> Tuple[jax.Array, jax.Array]:
        """x: (..., d_model) -> (y, aux).  Leading dims are flattened into
        the token axis for routing."""
        lead = x.shape[:-1]
        d = x.shape[-1]
        toks = x.reshape(-1, d)
        n = toks.shape[0]
        cap = self._capacity(n)
        dispatch, combine, aux = self._route(params["gate"], toks, cap)
        cdt = self.compute_dtype
        slots = jnp.einsum("nec,nd->ecd", dispatch.astype(cdt),
                           toks.astype(cdt))               # (E, C, d)
        if self.expert_axis is None:
            out = self._experts_ffn(params["experts"], slots)
        else:
            # (E, C, d) -> exchange -> (E_local, ep*C, d): each device
            # gathers every peer's slots for the experts it owns
            slots = lax.all_to_all(slots, self.expert_axis,
                                   split_axis=0, concat_axis=1, tiled=True)
            out = self._experts_ffn(params["experts"], slots)
            out = lax.all_to_all(out, self.expert_axis,
                                 split_axis=1, concat_axis=0, tiled=True)
        y = jnp.einsum("nec,ecd->nd", combine.astype(cdt), out)
        return y.reshape(*lead, d).astype(cdt), aux


# ---- routing without drops over the experts a layer holds -------------------

# what implements the grouped product ``rows of group e @ w[e]``:
#   "ragged"  jax.lax.ragged_dot (XLA's own; the CPU's only choice)
#   "gmm"     the Pallas grouped matmul (jax's megablox kernel): walks the
#             row tiles of the groups that have rows and reads no weight of
#             an expert no token reached
# "auto" takes the one the chip timing favoured at both of the serving
# shapes (a decode tick of 32 tokens, a prefill chunk of 1024; PERF.md)
GROUPED_IMPLS = ("auto", "ragged", "gmm")


def gmm_tiling(m: int, k: int, n: int):
    """Row / contraction / column tile of the Pallas grouped matmul at a
    problem size, from the chip timing (``tools/moe_grouped_timing.py``,
    PERF.md): few rows (a decode tick's 128) want a short row tile, so that
    an expert with one token multiplies 16 rows and not 128, a prefill
    chunk's 4096 rows want 256; the weight tiles are as wide as VMEM takes
    (2048 x 1024), because the product is bound by reading them."""
    tm = 256 if m >= 4096 else (128 if m >= 512 else 16)
    while m % tm:
        tm //= 2
    return tm, min(k, 2048), min(n, 1024)


def grouped_matmul(xs: jax.Array, w: jax.Array, group_sizes: jax.Array,
                   impl: str = "auto") -> jax.Array:
    """``xs`` (M, K) sorted by group, ``w`` (G, K, N), ``group_sizes`` (G,)
    int32 -> (M, N) float32: row r of group g times ``w[g]``.  Rows past
    ``group_sizes.sum()`` belong to no group; what comes back there is
    unspecified (the caller masks it)."""
    if impl == "auto":
        impl = "gmm" if jax.default_backend() == "tpu" else "ragged"
    if impl == "gmm":
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        return gmm(xs, w, group_sizes, preferred_element_type=jnp.float32,
                   tiling=gmm_tiling(xs.shape[0], w.shape[1], w.shape[2]),
                   interpret=jax.default_backend() != "tpu")
    return lax.ragged_dot(xs, w, group_sizes,
                          preferred_element_type=jnp.float32)


@dataclass(frozen=True)
class DroplessMoE(Module):
    """Top-k routing over ``n_experts`` with no capacity and no drops, of
    which this layer HOLDS a contiguous range ``held = (first, count)``:
    one chip's share of an expert-parallel deployment.

    The router keeps its full width and its k choices a token; the layer
    computes the part of the result that its own experts give, ``sum over
    the chosen e in held of w_e E_e(y)``, plus the shared expert, which
    every chip computes alike.  A token whose choices all lie elsewhere
    gets the shared expert's output alone.  What the absent experts would
    have added is NOT stood in for: on one chip the layer runs without its
    exchange.  ``held=None`` holds them all (the uncut layer).

    Experts are gated: ``E(y) = (silu(y W_gate) * (y W_in)) W_out``, no
    biases.  Tokens are sorted by expert and multiplied in one grouped
    product over the held experts (:func:`grouped_matmul`); the combine
    weights are applied on the way back.
    """

    d_model: int
    d_ff: int
    n_experts: int
    top_k: int = 1
    held: Optional[Tuple[int, int]] = None
    shared_ff: int = 0            # width of the shared expert (0 = none)
    routed_scale: float = 1.0
    score: str = "softmax"        # softmax | sigmoid (:func:`sigmoid_top_k`)
    impl: str = "auto"
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32

    def __post_init__(self):
        first, count = self.span
        if not (0 <= first and count >= 1
                and first + count <= self.n_experts):
            raise ValueError(f"held experts [{first}, {first + count}) lie "
                             f"outside the layer's {self.n_experts}")
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError(f"top_k must be in [1, {self.n_experts}], got "
                             f"{self.top_k}")
        if self.impl not in GROUPED_IMPLS:
            raise ValueError(f"impl must be one of {GROUPED_IMPLS}")
        if self.score not in ("softmax", "sigmoid"):
            raise ValueError(f"score must be softmax or sigmoid, got "
                             f"{self.score!r}")

    @property
    def span(self) -> Tuple[int, int]:
        return (0, self.n_experts) if self.held is None else self.held

    def init(self, key: jax.Array) -> Pytree:
        kg, k1, k2, k3, k4, k5, k6 = jax.random.split(key, 7)
        d, f, g = self.d_model, self.d_ff, self.span[1]
        bd, bf = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
        out = {
            "gate": {"w": _uniform(kg, (d, self.n_experts), bd,
                                   self.param_dtype)},
            "experts": {
                "w_gate": _uniform(k1, (g, d, f), bd, self.param_dtype),
                "w_in": _uniform(k2, (g, d, f), bd, self.param_dtype),
                "w_out": _uniform(k3, (g, f, d), bf, self.param_dtype)},
        }
        if self.score == "sigmoid":
            out["gate"]["bias"] = jnp.zeros((self.n_experts,),
                                            self.param_dtype)
        if self.shared_ff:
            s = self.shared_ff
            out["shared"] = {
                "w_gate": _uniform(k4, (d, s), bd, self.param_dtype),
                "w_in": _uniform(k5, (d, s), bd, self.param_dtype),
                "w_out": _uniform(k6, (s, d), 1.0 / math.sqrt(s),
                                  self.param_dtype)}
        return out

    def route(self, gate_params: Pytree, toks: jax.Array,
              mask: Optional[jax.Array]):
        """(N, d) -> the sorted dispatch: ``order`` (N*k,) assignment
        indices sorted by held expert (those that fell elsewhere, and those
        of masked tokens, last), ``sizes`` (count,) rows of each held
        expert, ``weight`` (N, k) combine weights with 0 where the choice
        is not computed here, and the router's ``probs`` / first choices
        for the load-balance term."""
        first, count = self.span
        if self.score == "sigmoid":
            weight, top_i, probs = sigmoid_top_k(gate_params, toks,
                                                 self.top_k)
        else:
            probs = router_probs(gate_params, toks)
            weight, top_i = top_k_weights(probs, self.top_k)
        local = top_i - first
        here = (local >= 0) & (local < count)
        if mask is not None:
            here = here & mask[:, None]
        gid = jnp.where(here, local, count).reshape(-1)
        order = jnp.argsort(gid, stable=True)
        sizes = jnp.zeros((count + 1,), jnp.int32).at[gid].add(1)[:count]
        weight = jnp.where(here, weight * self.routed_scale, 0.0)
        return order, sizes, weight, probs, top_i

    def experts_ffn(self, ep: Pytree, xs: jax.Array,
                    sizes: jax.Array) -> jax.Array:
        """Sorted rows (M, d) -> (M, d) float32 through each row's expert."""
        cdt = self.compute_dtype
        xs = xs.astype(cdt)
        gate = grouped_matmul(xs, ep["w_gate"].astype(cdt), sizes, self.impl)
        up = grouped_matmul(xs, ep["w_in"].astype(cdt), sizes, self.impl)
        h = (jax.nn.silu(gate) * up).astype(cdt)
        return grouped_matmul(h, ep["w_out"].astype(cdt), sizes, self.impl)

    def shared_ffn(self, sp: Pytree, toks: jax.Array) -> jax.Array:
        cdt = self.compute_dtype
        x = toks.astype(cdt)
        h = jax.nn.silu(jnp.matmul(x, sp["w_gate"].astype(cdt))) \
            * jnp.matmul(x, sp["w_in"].astype(cdt))
        return jnp.matmul(h, sp["w_out"].astype(cdt))

    def apply(self, params: Pytree, x: jax.Array, mask=None,
              return_load: bool = False, **kwargs):
        """x (..., d) -> (y, aux), or (y, aux, load) with ``return_load``:
        ``load`` (count,) int32 is how many assignments each held expert
        got.  ``mask`` (...,) bool leaves tokens out of the routed part
        (pad columns, idle lanes): they reach no expert and read none."""
        lead, d = x.shape[:-1], x.shape[-1]
        toks = x.reshape(-1, d)
        n, k = toks.shape[0], self.top_k
        with jax.named_scope("moe_route"):
            order, sizes, weight, probs, top_i = self.route(
                params["gate"], toks,
                None if mask is None else mask.reshape(-1))
            xs = jnp.take(toks, order // k, axis=0)          # (N*k, d)
        with jax.named_scope("moe_experts"):
            out = self.experts_ffn(params["experts"], xs, sizes)
        with jax.named_scope("moe_combine"):
            # rows past the held experts' groups carry whatever the
            # grouped product left there: zeroed, not weighted by zero
            rows = jnp.arange(n * k) < sizes.sum()
            out = jnp.where(rows[:, None], out, 0.0)
            back = jnp.zeros((n * k,), jnp.int32).at[order].set(
                jnp.arange(n * k, dtype=jnp.int32))
            y = (jnp.take(out, back, axis=0).reshape(n, k, d)
                 * weight[:, :, None]).sum(1)
        if self.shared_ff:
            with jax.named_scope("moe_shared"):
                y = y + self.shared_ffn(params["shared"], toks)
        # Switch / GShard load-balance term on the first choice, over all
        # of the layer's experts (1.0 when uniform)
        first_choice = jax.nn.one_hot(top_i[:, 0], self.n_experts,
                                      dtype=jnp.float32)
        aux = self.n_experts * jnp.sum(first_choice.mean(0) * probs.mean(0))
        y = y.reshape(*lead, d).astype(self.compute_dtype)
        return (y, aux, sizes) if return_load else (y, aux)
