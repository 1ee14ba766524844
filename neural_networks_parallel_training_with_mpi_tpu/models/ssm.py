"""A Mamba-2 mixer (state-space duality, Dao & Gu 2024), as the hybrid blocks
of Falcon-H1 run it beside their attention.

One token's pass, with ``H`` heads of width ``P``, ``G`` groups and a state of
``N`` numbers a head and channel:

* ``[z | xs | B | C | dt] = (u W_in) * m``: one projection, ``2 H P + 2 G N +
  H`` wide, each of the five segments times its own multiplier;
* ``[xs | B | C] <- silu(conv(...) + b)``: a causal depthwise convolution of
  ``d_conv`` taps over the ``H P + 2 G N`` channels (tap ``d_conv - 1`` is the
  token's own input);
* ``dt <- softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head; head ``j`` reads
  group ``j // (H / G)``'s ``B`` and ``C``:
  ``S_t = exp(dt_t A) S_{t-1} + dt_t xs_t B_t^T``, ``y_t = S_t C_t + D xs_t``;
* ``y <- y * silu(z)``, RMS-normed over each group's ``H P / G`` channels and
  scaled by a learned weight; then ``y W_out``.

The piece owns its projections and its **state row** (what one stream holds
in one layer between calls: the last ``d_conv - 1`` inputs of the convolution
and the float32 state ``S``), and three forms of the same mathematics:

* :meth:`apply`: a whole sequence from a zero state (the training forward);
* :meth:`apply_chunk`: a prefill chunk that starts from a carried state and
  returns the new one; pad columns (``valid`` False) move neither the state
  nor the convolution's tail;
* :meth:`apply_step`: one token a stream (a decode tick); lanes that are not
  ``active`` hold what they had, bit for bit.

The first two run the recurrence in its **chunked** form (scope ``ssm_scan``):
inside a tile of ``chunk`` positions everything is matrix products (``C B^T``
masked by the decays, times ``xs``), and only the tiles' states are carried
one after another.  ``chunk`` is a tile, not mathematics.  The decays, ``dt``
and the carried state are float32 whatever the compute type; ``xs``, ``B`` and
``C`` reach the products in the compute type, which accumulate in float32.
The tick (scope ``ssm_update``) is the recurrence as it stands.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .core import Linear, Module, Pytree, RMSNorm

F32 = jnp.float32


@dataclass(frozen=True)
class Mamba2Mixer(Module):
    d_model: int
    n_heads: int
    head_dim: int
    d_state: int
    n_groups: int = 1
    d_conv: int = 4
    chunk: int = 128
    # over the projection's five segments [z | xs | B | C | dt]
    multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    norm_eps: float = 1e-5
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.n_heads % self.n_groups or len(self.multipliers) != 5:
            raise ValueError(
                f"a mixer of {self.n_heads} heads needs a number of groups "
                f"that divides them (got {self.n_groups}) and five segment "
                f"multipliers (got {self.multipliers})")

    # ---- shapes ---------------------------------------------------------
    @property
    def d_ssm(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_ssm + 2 * self.n_groups * self.d_state

    @property
    def in_dim(self) -> int:
        return self.d_ssm + self.conv_dim + self.n_heads

    def state_row(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """What one stream holds in one layer between calls: store name ->
        (shape, type).  The paged server asks this as it asks the attention
        for its ``cache_row()``; the state is per stream, not per token."""
        return {"conv": ((self.d_conv - 1, self.conv_dim),
                         self.compute_dtype),
                "ssm": ((self.n_heads, self.head_dim, self.d_state), F32)}

    def zero_state(self, batch: int) -> Dict[str, jax.Array]:
        return {n: jnp.zeros((batch,) + s, t)
                for n, (s, t) in self.state_row().items()}

    def _mods(self):
        lin = lambda i, o: Linear(i, o, use_bias=False,       # noqa: E731
                                  param_dtype=self.param_dtype,
                                  compute_dtype=self.compute_dtype)
        return {"in_proj": lin(self.d_model, self.in_dim),
                "norm": RMSNorm(self.d_ssm // self.n_groups, self.norm_eps,
                                self.param_dtype),
                "out_proj": lin(self.d_ssm, self.d_model)}

    def init(self, key: jax.Array) -> Pytree:
        """Mamba-2's initialisation: ``dt_bias`` the inverse softplus of a
        log-uniform draw in [0.001, 0.1], ``A_log = log U[1, 16]``, ``D = 1``,
        the convolution as torch's ``Conv1d``."""
        mods = self._mods()
        k_in, k_out, k_w, k_b, k_dt, k_a = jax.random.split(key, 6)
        pdt, h = self.param_dtype, self.n_heads
        bound = 1.0 / math.sqrt(self.d_conv)
        dt = jnp.exp(jax.random.uniform(k_dt, (h,), F32, math.log(1e-3),
                                        math.log(1e-1)))
        return {
            "in_proj": mods["in_proj"].init(k_in),
            "conv": {"w": jax.random.uniform(
                         k_w, (self.d_conv, self.conv_dim), pdt, -bound,
                         bound),
                     "b": jax.random.uniform(k_b, (self.conv_dim,), pdt,
                                             -bound, bound)},
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pdt),
            "A_log": jnp.log(jax.random.uniform(k_a, (h,), F32, 1.0,
                                                16.0)).astype(pdt),
            "D": jnp.ones((h,), pdt),
            "norm": {"scale": jnp.ones((self.d_ssm,), pdt)},
            "out_proj": mods["out_proj"].init(k_out),
        }

    # ---- the pieces -----------------------------------------------------
    def _project(self, params: Pytree, u: jax.Array):
        """``u`` (B, W, d) -> ``z`` (B, W, H P), the convolution's input
        (B, W, conv_dim) and ``dt`` before its bias (B, W, H)."""
        with jax.named_scope("ssm_in"):
            p = self._mods()["in_proj"].apply(params["in_proj"], u)
            if any(m != 1.0 for m in self.multipliers):
                widths = (self.d_ssm, self.d_ssm,
                          self.n_groups * self.d_state,
                          self.n_groups * self.d_state, self.n_heads)
                p = p * jnp.asarray(np.repeat(
                    np.asarray(self.multipliers, np.float32), widths),
                    p.dtype)
            return (p[..., :self.d_ssm],
                    p[..., self.d_ssm:self.d_ssm + self.conv_dim],
                    p[..., self.d_ssm + self.conv_dim:])

    def _conv(self, params: Pytree, window: jax.Array, width: int):
        """``silu(conv + b)`` at the last ``width`` positions of ``window``
        (B, d_conv - 1 + width, conv_dim): the inputs with the ``d_conv - 1``
        before them.  Split into ``xs`` (B, W, H, P) and ``B``, ``C`` (B, W,
        G, N), in the compute type."""
        w = params["conv"]["w"].astype(F32)
        acc = params["conv"]["b"].astype(F32)
        for k in range(self.d_conv):
            acc = acc + window[:, k:k + width].astype(F32) * w[k]
        out = jax.nn.silu(acc).astype(self.compute_dtype)
        b, gn = out.shape[0], self.n_groups * self.d_state
        return (out[..., :self.d_ssm].reshape(b, width, self.n_heads,
                                              self.head_dim),
                out[..., self.d_ssm:self.d_ssm + gn].reshape(
                    b, width, self.n_groups, self.d_state),
                out[..., self.d_ssm + gn:].reshape(
                    b, width, self.n_groups, self.d_state))

    def _steps(self, params: Pytree, dt: jax.Array):
        """``softplus(dt + dt_bias)`` and ``A = -exp(A_log)``, float32."""
        return (jax.nn.softplus(dt.astype(F32)
                                + params["dt_bias"].astype(F32)),
                -jnp.exp(params["A_log"].astype(F32)))

    def _finish(self, params: Pytree, y: jax.Array, xs: jax.Array,
                z: jax.Array) -> jax.Array:
        """``y`` (B, W, H, P) float32 from the recurrence -> the mixer's
        output (B, W, d): the skip ``D xs``, the gate, the group norm (scope
        ``ssm_gate_norm``), then ``W_out`` (scope ``ssm_out``)."""
        mods = self._mods()
        b, w = y.shape[:2]
        with jax.named_scope("ssm_gate_norm"):
            y = y + params["D"].astype(F32)[:, None] * xs.astype(F32)
            y = y.reshape(b, w, self.d_ssm) * jax.nn.silu(z.astype(F32))
            groups = y.reshape(b, w, self.n_groups, -1)
            y = mods["norm"].apply(
                {"scale": params["norm"]["scale"].reshape(self.n_groups, -1)},
                groups).reshape(b, w, self.d_ssm)
        with jax.named_scope("ssm_out"):
            return mods["out_proj"].apply(params["out_proj"],
                                          y.astype(self.compute_dtype))

    def _scan(self, xs, bm, cm, dt, a, state):
        """The recurrence over a run of positions in its chunked form.
        ``xs`` (B, T, H, P), ``bm`` / ``cm`` (B, T, G, N), ``dt`` (B, T, H)
        float32 (0 at a position that is to move nothing), ``a`` (H,),
        ``state`` (B, H, P, N) float32 -> (``y`` (B, T, H, P) float32 without
        the skip, the state after the last position)."""
        b, t, h, p = xs.shape
        g, n, j = self.n_groups, self.d_state, h // self.n_groups
        q = min(self.chunk, t)
        pad = -t % q
        if pad:     # positions that move nothing: dt 0, inputs 0
            widen = lambda v: jnp.pad(                         # noqa: E731
                v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
            xs, bm, cm, dt = widen(xs), widen(bm), widen(cm), widen(dt)
        c = (t + pad) // q
        cdt = self.compute_dtype
        x6 = xs.reshape(b, c, q, g, j, p)
        bm, cm = bm.reshape(b, c, q, g, n), cm.reshape(b, c, q, g, n)
        # head-major (B, C, G, J, Q): the log decays and their running sum
        dth = dt.reshape(b, c, q, g, j).transpose(0, 1, 3, 4, 2)
        cs = jnp.cumsum(dth * a.reshape(g, j, 1), axis=-1)
        # inside a tile: y_q += sum_{s <= q} (C_q . B_s) e^{cs_q - cs_s}
        # dt_s xs_s
        cb = jnp.einsum("bcqgn,bcsgn->bcgqs", cm, bm,
                        preferred_element_type=F32)
        seen = jnp.tril(jnp.ones((q, q), bool))
        decay = jnp.exp(jnp.where(seen, cs[..., :, None] - cs[..., None, :],
                                  -jnp.inf))
        m = cb[:, :, :, None] * decay * dth[..., None, :]
        y = jnp.einsum("bcgjqs,bcsgjp->bcqgjp", m.astype(cdt), x6,
                       preferred_element_type=F32)
        # what a tile adds to the state: sum_s e^{cs_end - cs_s} dt_s xs_s
        # B_s^T
        to_end = (jnp.exp(cs[..., -1:] - cs) * dth).transpose(0, 1, 4, 2, 3)
        added = jnp.einsum(
            "bcqgjp,bcqgn->bcgjpn",
            (x6.astype(F32) * to_end[..., None]).astype(cdt), bm,
            preferred_element_type=F32)
        whole = jnp.exp(cs[..., -1])                    # (B, C, G, J)

        def carry(s, tile):
            keep, add = tile
            return s * keep[..., None, None] + add, s

        last, entering = jax.lax.scan(
            carry, state.reshape(b, g, j, p, n),
            (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(added, 1, 0)))
        # from the state a tile starts with: y_q += e^{cs_q} C_q . S
        y = y + jnp.einsum(
            "bcqgn,bcgjpn->bcqgjp", cm.astype(F32),
            jnp.moveaxis(entering, 0, 1),
            preferred_element_type=F32) * jnp.exp(cs).transpose(
                0, 1, 4, 2, 3)[..., None]
        return (y.reshape(b, t + pad, h, p)[:, :t],
                last.reshape(b, h, p, n))

    # ---- the three forms ------------------------------------------------
    def apply_chunk(self, params: Pytree, u: jax.Array,
                    state: Dict[str, jax.Array], valid: jax.Array):
        """``u`` (B, W, d), ``state`` as :meth:`state_row` with a leading B,
        ``valid`` (W,) bool, the true columns first -> (output (B, W, d), the
        state after the last true column).  A pad column's output is
        discarded by the caller."""
        w = u.shape[1]
        z, xbc, dt = self._project(params, u)
        with jax.named_scope("ssm_conv"):
            window = jnp.concatenate(
                [state["conv"].astype(xbc.dtype), xbc], axis=1)
            xs, bm, cm = self._conv(params, window, w)
            # the last d_conv - 1 TRUE inputs (the old tail's where the
            # chunk holds fewer)
            tail = jax.lax.dynamic_slice_in_dim(
                window, valid.sum(), self.d_conv - 1, axis=1)
        with jax.named_scope("ssm_scan"):
            dt, a = self._steps(params, dt)
            dt = jnp.where(valid[None, :, None], dt, 0.0)
            y, ssm = self._scan(xs, bm, cm, dt, a, state["ssm"])
        return (self._finish(params, y, xs, z),
                {"conv": tail.astype(state["conv"].dtype), "ssm": ssm})

    def apply(self, params: Pytree, u: jax.Array, **kwargs) -> jax.Array:
        """The full causal forward over ``u`` (B, T, d) from a zero state:
        ``Mixer(u)`` (the norm before and the residual after are the
        block's)."""
        return self.apply_chunk(params, u, self.zero_state(u.shape[0]),
                                jnp.ones((u.shape[1],), bool))[0]

    def apply_step(self, params: Pytree, u: jax.Array,
                   state: Dict[str, jax.Array], active: jax.Array):
        """One token a stream: ``u`` (S, 1, d), ``state`` with a leading S,
        ``active`` (S,) bool -> (output (S, 1, d), the new state).  A lane
        that is not active keeps its state and its tail bit for bit."""
        z, xbc, dt = self._project(params, u)
        g, j = self.n_groups, self.n_heads // self.n_groups
        with jax.named_scope("ssm_conv"):
            window = jnp.concatenate(
                [state["conv"].astype(xbc.dtype), xbc], axis=1)
            xs, bm, cm = self._conv(params, window, 1)
            tail = jnp.where(active[:, None, None],
                             window[:, 1:].astype(state["conv"].dtype),
                             state["conv"])
        with jax.named_scope("ssm_update"):
            dt, a = self._steps(params, dt)
            dt = dt[:, 0].reshape(-1, g, j)                 # (S, G, J)
            x = xs[:, 0].astype(F32).reshape(
                -1, g, j, self.head_dim)                    # (S, G, J, P)
            b1, c1 = bm[:, 0].astype(F32), cm[:, 0].astype(F32)  # (S, G, N)
            old = state["ssm"].reshape(-1, g, j, self.head_dim, self.d_state)
            new = (old * jnp.exp(dt * a.reshape(g, j))[..., None, None]
                   + (dt[..., None] * x)[..., None]
                   * b1[:, :, None, None, :])
            y = (new * c1[:, :, None, None, :]).sum(-1)     # (S, G, J, P)
            ssm = jnp.where(active[:, None, None, None, None], new,
                            old).reshape(state["ssm"].shape)
        y = y.reshape(-1, 1, self.n_heads, self.head_dim)
        return (self._finish(params, y, xs, z), {"conv": tail, "ssm": ssm})

    # ---- counts ---------------------------------------------------------
    def fwd_flops_per_token(self) -> float:
        """Products of one token: the two projections, and the chunked
        recurrence at the tile ``chunk`` (``C B^T``, its product with
        ``xs``, the tile's state and the read of the entering one)."""
        q, n, g = self.chunk, self.d_state, self.n_groups
        h, p = self.n_heads, self.head_dim
        return (2.0 * self.d_model * (self.in_dim + self.d_ssm)
                + 2.0 * q * n * g + h * (2.0 * q * p + 4.0 * p * n))
