"""A Gated DeltaNet mixer (Yang, Kautz and Hatamizadeh, "Gated Delta
Networks", arXiv:2412.06464), as the linear-attention layers of Olmo-Hybrid run
it in place of attention.

One token's pass, with ``H`` heads, keys of ``dk`` and values of ``dv``:

* ``[q | k | v | g | b | a] = u W_in``: one projection, ``2 H dk + 2 H dv + 2
  H`` wide;
* ``[q | k | v] <- silu(conv(...))``: a causal depthwise convolution of
  ``d_conv`` taps without bias over the ``2 H dk + H dv`` channels (the three
  short convolutions side by side; tap ``d_conv - 1`` is the token's own
  input);
* ``q`` and ``k`` L2-normed a head, ``q`` times ``1 / sqrt(dk)``;
* ``beta = sigmoid(b)`` a head, times 2 under ``allow_neg_eigval`` (so the
  transition ``I - beta k k^T`` may have a negative eigenvalue);
  ``alpha = exp(g_t)`` with ``g_t = -exp(A_log) softplus(a + dt_bias)``;
* the state ``S`` (``dk x dv`` a head, float32):
  ``S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T``,
  ``o_t = S_t^T q_t``; as a tick computes it: ``S' = alpha S``, ``u = beta (v
  - S'^T k)``, ``S = S' + k u^T``, ``o = S^T q``;
* ``o <- RMSNorm_dv(o) * silu(g)`` a head (one learned weight of ``dv`` for
  all heads), then ``o W_out``.

The piece owns its projections and its **state row** (what one stream holds
in one layer between calls: the last ``d_conv - 1`` inputs of the convolution
and the float32 state ``S``), and three forms of the same mathematics.  A row
of ``S`` is ``dv`` wide; where that is not whole 128-lane tiles the store lays
``fold`` rows side by side (``S`` of 96 x 192 is stored 48 x 384: the same
bytes, in a layout the chip does not pad from 192 lanes to 256).

* :meth:`apply`: a whole sequence from a zero state (the training forward);
* :meth:`apply_chunk`: a prefill chunk that starts from a carried state and
  returns the new one; pad columns (``valid`` False) move neither the state
  nor the convolution's tail;
* :meth:`apply_step`: one token a stream (a decode tick); lanes that are not
  ``active`` hold what they had, bit for bit.

The first two run the rule in its **chunked** form (scope ``gdn_scan``; the
WY / UT form of arXiv:2406.06484 with the gate): inside a tile of ``chunk``
positions, with ``G`` the running sum of the log decays and ``Gamma[t, s] =
exp(G_t - G_s)``, ``A = strictly_lower(diag(beta) (Gamma * K K^T))`` and ``T
= (I + A)^-1 diag(beta)`` by one triangular solve; ``T`` applied to ``exp(G)
K`` and to ``V`` gives ``W`` and ``U_v``, and only the tiles' states are
carried one after another: ``U = U_v - W S``, ``O = exp(G) Q S + (Gamma * Q
K^T, lower) U``, ``S <- exp(G_end) S + (exp(G_end - G) K)^T U``.  ``chunk`` is
a tile, not mathematics: 64, as FLA's kernels.  Everything from the
convolution's output on is float32, and every product of the rule asks for
``HIGHEST`` precision (a TPU's default rounds float32 operands to bfloat16 for
one pass, which would keep the state at bfloat16's precision through a
prefill).  The tick (scope ``gdn_update``) is the rule as it stands, float32
on the VPU: in XLA one pass over a layer's state for ``S^T k`` and ``S^T q``
and one that writes ``S'`` (it needs ``u``, which needs ``S^T k``); on the
paged server's fused path one Pallas pass that reads the state once and
writes it once (``ops.pallas_kernels.delta_rule_step``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.pallas_kernels import delta_rule_block, delta_rule_step
from ..utils import compile_ledger as ledger_lib
from .core import Linear, Module, Pytree, RMSNorm

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6       # FLA's l2norm: x / sqrt(sum(x^2) + eps)


def l2_normed(x: jax.Array) -> jax.Array:
    """``x`` over its last axis to unit length (float32)."""
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + L2_EPS)


@dataclass(frozen=True)
class GatedDeltaNet(Module):
    d_model: int
    n_heads: int
    key_dim: int
    value_dim: int
    d_conv: int = 4
    chunk: int = 64
    allow_neg_eigval: bool = False
    norm_eps: float = 1e-6
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32

    # ---- shapes ---------------------------------------------------------
    @property
    def qk_width(self) -> int:
        return self.n_heads * self.key_dim

    @property
    def v_width(self) -> int:
        return self.n_heads * self.value_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.qk_width + self.v_width

    @property
    def in_dim(self) -> int:
        return self.conv_dim + self.v_width + 2 * self.n_heads

    @property
    def fold(self) -> int:
        """Rows of ``S`` laid side by side in a stored row: the fewest that
        divide ``dk`` and fill whole 128-lane tiles (1 where none do)."""
        return next((f for f in range(1, self.key_dim + 1)
                     if self.key_dim % f == 0
                     and f * self.value_dim % 128 == 0), 1)

    def state_row(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """What one stream holds in one layer between calls: store name ->
        (shape, type).  The paged server asks this as it asks the attention
        for its ``cache_row()``; the state is per stream, not per token."""
        f = self.fold
        return {"conv": ((self.d_conv - 1, self.conv_dim),
                         self.compute_dtype),
                "state": ((self.n_heads, self.key_dim // f,
                           f * self.value_dim), F32)}

    def zero_state(self, batch: int) -> Dict[str, jax.Array]:
        return {n: jnp.zeros((batch,) + s, t)
                for n, (s, t) in self.state_row().items()}

    def _mods(self):
        lin = lambda i, o: Linear(i, o, use_bias=False,       # noqa: E731
                                  param_dtype=self.param_dtype,
                                  compute_dtype=self.compute_dtype)
        return {"in_proj": lin(self.d_model, self.in_dim),
                "norm": RMSNorm(self.value_dim, self.norm_eps,
                                self.param_dtype),
                "out_proj": lin(self.v_width, self.d_model)}

    def init(self, key: jax.Array) -> Pytree:
        """FLA's initialisation: ``A_log = log U[1, 16]``, ``dt_bias`` the
        inverse softplus of a log-uniform draw in [0.001, 0.1], the
        convolution as torch's ``Conv1d`` without bias."""
        mods = self._mods()
        k_in, k_out, k_w, k_dt, k_a = jax.random.split(key, 5)
        pdt, h = self.param_dtype, self.n_heads
        bound = 1.0 / math.sqrt(self.d_conv)
        dt = jnp.exp(jax.random.uniform(k_dt, (h,), F32, math.log(1e-3),
                                        math.log(1e-1)))
        return {
            "in_proj": mods["in_proj"].init(k_in),
            "conv": {"w": jax.random.uniform(
                k_w, (self.d_conv, self.conv_dim), pdt, -bound, bound)},
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pdt),
            "A_log": jnp.log(jax.random.uniform(k_a, (h,), F32, 1.0,
                                                16.0)).astype(pdt),
            "norm": {"scale": jnp.ones((self.value_dim,), pdt)},
            "out_proj": mods["out_proj"].init(k_out),
        }

    # ---- the pieces -----------------------------------------------------
    def _project(self, params: Pytree, u: jax.Array):
        """``u`` (B, W, d) -> the convolution's input (B, W, conv_dim), the
        output gate (B, W, H dv), ``b`` and ``a`` (B, W, H)."""
        with jax.named_scope("gdn_in"):
            p = self._mods()["in_proj"].apply(params["in_proj"], u)
            c, v, h = self.conv_dim, self.v_width, self.n_heads
            return (p[..., :c], p[..., c:c + v], p[..., c + v:c + v + h],
                    p[..., c + v + h:])

    def _conv(self, params: Pytree, window: jax.Array, width: int):
        """``silu(conv)`` at the last ``width`` positions of ``window`` (B,
        d_conv - 1 + width, conv_dim): the inputs with the ``d_conv - 1``
        before them.  -> ``q``, ``k`` (B, W, H, dk) L2-normed (``q`` scaled)
        and ``v`` (B, W, H, dv), float32."""
        w = params["conv"]["w"].astype(F32)
        acc = window[:, :width].astype(F32) * w[0]
        for tap in range(1, self.d_conv):
            acc = acc + window[:, tap:tap + width].astype(F32) * w[tap]
        out = jax.nn.silu(acc)
        b, h, dk, qk = out.shape[0], self.n_heads, self.key_dim, self.qk_width
        q = l2_normed(out[..., :qk].reshape(b, width, h, dk))
        k = l2_normed(out[..., qk:2 * qk].reshape(b, width, h, dk))
        v = out[..., 2 * qk:].reshape(b, width, h, self.value_dim)
        return q * (1.0 / math.sqrt(dk)), k, v

    def _gates(self, params: Pytree, b: jax.Array, a: jax.Array):
        """``beta`` and the log decay ``g`` (both (..., H), float32)."""
        beta = jax.nn.sigmoid(b.astype(F32))
        if self.allow_neg_eigval:
            beta = beta * 2.0
        g = -jnp.exp(params["A_log"].astype(F32)) * jax.nn.softplus(
            a.astype(F32) + params["dt_bias"].astype(F32))
        return beta, g

    def _finish(self, params: Pytree, o: jax.Array,
                gate: jax.Array) -> jax.Array:
        """``o`` (B, W, H, dv) float32 from the rule -> the mixer's output
        (B, W, d): the per-head norm times ``silu`` of the gate (scope
        ``gdn_gate_norm``), then ``W_out`` (scope ``gdn_out``)."""
        mods = self._mods()
        b, w = o.shape[:2]
        with jax.named_scope("gdn_gate_norm"):
            o = mods["norm"].apply(params["norm"], o).reshape(
                b, w, self.v_width) * jax.nn.silu(gate.astype(F32))
        with jax.named_scope("gdn_out"):
            return mods["out_proj"].apply(params["out_proj"],
                                          o.astype(self.compute_dtype))

    def _scan(self, q, k, v, beta, g, state):
        """The rule over a run of positions in its chunked form.  ``q``,
        ``k`` (B, T, H, dk), ``v`` (B, T, H, dv), ``beta``, ``g`` (B, T, H),
        all float32 (``beta`` and ``g`` 0 at a position that is to move
        nothing), ``state`` (B, H, dk, dv) float32 -> (``o`` (B, T, H, dv)
        float32, the state after the last position)."""
        b, t, h, dk = k.shape
        c = min(self.chunk, t)
        pad = -t % c
        if pad:     # positions that move nothing: beta 0, g 0, inputs 0
            widen = lambda x: jnp.pad(                         # noqa: E731
                x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            q, k, v, beta, g = map(widen, (q, k, v, beta, g))
        n = (t + pad) // c

        def tiles(x):       # (B, T, H, ...) -> (B, N, H, C, ...)
            return jnp.swapaxes(x.reshape(b, n, c, *x.shape[2:]), 2, 3)

        q, k, v, beta, g = map(tiles, (q, k, v, beta, g))
        cum = jnp.cumsum(g, axis=-1)                        # G, (B, N, H, C)
        seen = jnp.tril(jnp.ones((c, c), bool))
        before = jnp.tril(jnp.ones((c, c), bool), -1)
        gamma = jnp.exp(jnp.where(seen, cum[..., :, None] - cum[..., None, :],
                                  -jnp.inf))
        # (I + A) [W | U_v] = diag(beta) [exp(G) K | V], A strictly lower
        kk = jnp.einsum("...td,...sd->...ts", k, k, precision=HIGHEST)
        a = jnp.where(before, beta[..., None] * gamma * kk, 0.0)
        rhs = jnp.concatenate([k * (beta * jnp.exp(cum))[..., None],
                               v * beta[..., None]], axis=-1)
        solved = jax.lax.linalg.triangular_solve(
            a, rhs, left_side=True, lower=True, unit_diagonal=True)
        w, uv = solved[..., :dk], solved[..., dk:]
        qk = jnp.einsum("...td,...sd->...ts", q, k, precision=HIGHEST)
        p = jnp.where(seen, gamma * qk, 0.0)
        qe = q * jnp.exp(cum)[..., None]
        kd = k * jnp.exp(cum[..., -1:] - cum)[..., None]
        whole = jnp.exp(cum[..., -1])                       # (B, N, H)

        def carry(s, tile):
            w, uv, qe, p, kd, keep = tile
            u = uv - jnp.einsum("bhck,bhkv->bhcv", w, s, precision=HIGHEST)
            o = (jnp.einsum("bhck,bhkv->bhcv", qe, s, precision=HIGHEST)
                 + jnp.einsum("bhcs,bhsv->bhcv", p, u, precision=HIGHEST))
            s = s * keep[..., None, None] + jnp.einsum(
                "bhck,bhcv->bhkv", kd, u, precision=HIGHEST)
            return s, o

        last, o = jax.lax.scan(
            carry, state,
            tuple(jnp.moveaxis(x, 1, 0) for x in (w, uv, qe, p, kd, whole)))
        # (N, B, H, C, dv) -> (B, T, H, dv)
        o = jnp.moveaxis(o, 0, 1).swapaxes(2, 3).reshape(b, n * c, h, -1)
        return o[:, :t], last

    # ---- the three forms ------------------------------------------------
    def apply_chunk(self, params: Pytree, u: jax.Array,
                    state: Dict[str, jax.Array], valid: jax.Array):
        """``u`` (B, W, d), ``state`` as :meth:`state_row` with a leading B,
        ``valid`` (W,) bool, the true columns first -> (output (B, W, d), the
        state after the last true column).  A pad column's output is
        discarded by the caller."""
        w = u.shape[1]
        xqkv, gate, b, a = self._project(params, u)
        with jax.named_scope("gdn_conv"):
            window = jnp.concatenate(
                [state["conv"].astype(xqkv.dtype), xqkv], axis=1)
            q, k, v = self._conv(params, window, w)
            # the last d_conv - 1 TRUE inputs (the old tail's where the
            # chunk holds fewer)
            tail = jax.lax.dynamic_slice_in_dim(
                window, valid.sum(), self.d_conv - 1, axis=1)
        with jax.named_scope("gdn_scan"):
            beta, g = self._gates(params, b, a)
            live = valid[None, :, None]
            beta, g = jnp.where(live, beta, 0.0), jnp.where(live, g, 0.0)
            stored = state["state"]
            o, s = self._scan(q, k, v, beta, g, stored.reshape(
                stored.shape[:2] + (self.key_dim, self.value_dim)))
        return (self._finish(params, o, gate),
                {"conv": tail.astype(state["conv"].dtype),
                 "state": s.reshape(stored.shape)})

    def apply(self, params: Pytree, u: jax.Array, **kwargs) -> jax.Array:
        """The full causal forward over ``u`` (B, T, d) from a zero state
        (the norm and the residual around it are the block's)."""
        return self.apply_chunk(params, u, self.zero_state(u.shape[0]),
                                jnp.ones((u.shape[1],), bool))[0]

    def apply_step(self, params: Pytree, u: jax.Array,
                   state: Dict[str, jax.Array], active: jax.Array,
                   fused: bool = False):
        """One token a stream: ``u`` (S, 1, d), ``state`` with a leading S,
        ``active`` (S,) bool -> (output (S, 1, d), the new state).  A lane
        that is not active keeps its state and its tail bit for bit.
        ``fused`` (the paged server's fused path) runs the update as one
        Pallas pass over each stream's state
        (``ops.pallas_kernels.delta_rule_step``); else XLA's two passes
        below.  The program's compile-ledger event says which ran."""
        xqkv, gate, b, a = self._project(params, u)
        with jax.named_scope("gdn_conv"):
            window = jnp.concatenate(
                [state["conv"].astype(xqkv.dtype), xqkv], axis=1)
            q, k, v = self._conv(params, window, 1)
            tail = jnp.where(active[:, None, None],
                             window[:, 1:].astype(state["conv"].dtype),
                             state["conv"])
        with jax.named_scope("gdn_update"):
            beta, g = self._gates(params, b[:, 0], a[:, 0])    # (S, H)
            old = state["state"]                        # (S, H, dk/f, f dv)
            if fused:
                ledger_lib.note("gdn_update", {
                    "impl": "pallas",
                    "block": [delta_rule_block(*old.shape), self.n_heads]})
                s, o = delta_rule_step(old, k[:, 0], q[:, 0], v[:, 0], beta,
                                       jnp.exp(g), active)
            else:
                ledger_lib.note("gdn_update", {"impl": "xla"})
                s, o = self._update(old, k[:, 0], q[:, 0], v[:, 0], beta, g,
                                    active)
        return (self._finish(params, o[:, None], gate),
                {"conv": tail, "state": s})

    def _update(self, old, k1, q1, v1, beta, g, active):
        """The tick's rule in XLA: ``old`` (S, H, dk/f, f dv), ``k1``,
        ``q1`` (S, H, dk), ``v1`` (S, H, dv), ``beta``, ``g`` (S, H) ->
        (the new state, ``o`` (S, H, dv))."""
        f, dv = self.fold, self.value_dim
        decay = jnp.exp(g)[..., None]                           # (S, H, 1)
        lane_row = jax.lax.broadcasted_iota(jnp.int32, (f * dv,), 0) // dv

        def spread(x):
            """(S, H, dk) -> (S, H, dk/f, 1 or f dv), x[i] in the lanes of a
            stored row that hold S[i]: a select over the lanes of a
            broadcast, which the compiler fuses into the pass over the
            state (a reshape of a broadcast it would write out)."""
            x = x.reshape(x.shape[:2] + (self.key_dim // f, f))
            out = x[..., :1]
            for j in range(1, f):
                out = jnp.where(lane_row == j, x[..., j:j + 1], out)
            return out

        def gather(r):          # (S, H, f dv) -> (S, H, dv): the f rows' sum
            return r.reshape(r.shape[:2] + (f, dv)).sum(-2)

        # one pass reads the state for both products, S^T k and S^T q;
        # o = S'^T q = alpha S^T q + (k . q) u for S' = alpha S + k u^T
        sk = gather((old * spread(k1)).sum(-2))
        sq = gather((old * spread(q1)).sum(-2))
        u1 = beta[..., None] * (v1 - decay * sk)                # (S, H, dv)
        o = decay * sq + (k1 * q1).sum(-1, keepdims=True) * u1
        # the second writes S'; a lane that is not active keeps S
        s = jnp.where(active[:, None, None, None],
                      old * decay[..., None]
                      + spread(k1) * jnp.tile(u1, f)[..., None, :], old)
        return s, o

    # ---- counts ---------------------------------------------------------
    def recurrence_flops_per_token(self) -> float:
        """The chunked rule's products of one token at the tile ``chunk``
        (C): ``K K^T`` and ``Q K^T`` (2 C dk each), ``T`` applied to ``K``
        and ``V`` counted as a product (2 C (dk + dv)), ``W S``, ``exp(G) Q
        S`` and the tile's state (2 dk dv each), and ``P U`` (2 C dv), a
        head."""
        c, dk, dv = self.chunk, self.key_dim, self.value_dim
        return self.n_heads * (4.0 * c * dk + 2.0 * c * (dk + dv)
                               + 6.0 * dk * dv + 2.0 * c * dv)

    def fwd_flops_per_token(self) -> float:
        """Products of one token: the two projections and the rule."""
        return (2.0 * self.d_model * (self.in_dim + self.v_width)
                + self.recurrence_flops_per_token())
