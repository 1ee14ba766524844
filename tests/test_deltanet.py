"""The Gated DeltaNet mixer (``models/deltanet.py``) against the delta rule as
it is written, one step a token (``delta_rule`` and ``gdn`` of
``benchmark/families/olmo_hybrid.py``, which import nothing from the program),
at a toy size on the CPU.

Everything here is float32 on both sides and seeded.  The program's chunked
form (a triangular solve inside each tile, the tiles' states carried) and the
reference's step-by-step product with ``alpha (I - beta k k^T)`` are the same
sums in another order: ``TIGHT`` = 1e-5 of the tensor's scale covers that
(observed under 1e-6).  Every draw puts some ``beta`` above 1, where a
transition has a negative eigenvalue.
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.families import olmo_hybrid as ref                 # noqa: E402
from neural_networks_parallel_training_with_mpi_tpu.models.deltanet import (  # noqa: E402
    GatedDeltaNet,
)

TIGHT = 1e-5
D, H, DK, DV = 32, 3, 8, 64
MODEL = {"gdn_key_heads": H, "gdn_value_heads": H, "gdn_key_dim": DK,
         "gdn_value_dim": DV, "gdn_conv": 4, "gdn_neg_eigval": True,
         "rms_eps": 1e-6, "config": "toy"}


def close(a, b, tol=TIGHT):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-30)


def draw(seed: int, t: int, b: int = 2):
    """What the rule reads: unit keys, queries scaled as the mixer scales
    them, values, ``beta`` in (0.2, 1.9) and log decays in (-0.6, -0.01)."""
    kq, kk, kv, kb, kg = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(kq, (b, t, H, DK))) * DK ** -0.5
    k = unit(jax.random.normal(kk, (b, t, H, DK)))
    v = jax.random.normal(kv, (b, t, H, DV))
    beta = jax.random.uniform(kb, (b, t, H), minval=0.2, maxval=1.9)
    g = -jax.random.uniform(kg, (b, t, H), minval=0.01, maxval=0.6)
    assert (beta > 1).any()
    return q, k, v, beta, g


def sequential(q, k, v, beta, g):
    with jax.default_matmul_precision("highest"):
        return ref.delta_rule(q, k, v, beta, jnp.exp(g))


def zero(b=2):
    return jnp.zeros((b, H, DK, DV), jnp.float32)


@pytest.mark.parametrize("length,chunk", [(8, 8), (24, 8), (13, 8), (37, 16),
                                          (5, 64), (64, 64), (70, 16)])
def test_the_chunked_form_is_the_token_by_token_rule(length, chunk):
    """Whole tiles and tiles cut short, one tile and many."""
    q, k, v, beta, g = draw(length, length)
    o, _ = GatedDeltaNet(D, H, DK, DV, chunk=chunk)._scan(q, k, v, beta, g,
                                                          zero())
    assert o.shape == (2, length, H, DV)
    assert close(o, sequential(q, k, v, beta, g))


def test_a_carried_state_over_two_chunks_is_one_pass():
    q, k, v, beta, g = draw(3, 40)
    mixer = GatedDeltaNet(D, H, DK, DV, chunk=8)
    whole, end = mixer._scan(q, k, v, beta, g, zero())
    cut = lambda x, a, b: x[:, a:b]                              # noqa: E731
    first, mid = mixer._scan(*(cut(x, 0, 19) for x in (q, k, v, beta, g)),
                             zero())
    second, last = mixer._scan(*(cut(x, 19, 40) for x in (q, k, v, beta, g)),
                               mid)
    assert close(jnp.concatenate([first, second], axis=1), whole)
    assert close(last, end)


@pytest.fixture(scope="module")
def mixer_and_params():
    mixer = GatedDeltaNet(D, H, DK, DV, chunk=8, allow_neg_eigval=True)
    params = mixer.init(jax.random.PRNGKey(0))
    # a spread of the projection's b under which some beta pass 1
    params["in_proj"]["w"] = params["in_proj"]["w"] * 4.0
    return mixer, params


def reference_tensors(params):
    return {"gdn_in.w": params["in_proj"]["w"], "conv.w": params["conv"]["w"],
            "dt_bias": params["dt_bias"], "A_log": params["A_log"],
            "gdn_norm.scale": params["norm"]["scale"],
            "gdn_out.w": params["out_proj"]["w"]}


def test_the_mixer_is_the_reference_and_beta_passes_one(mixer_and_params):
    mixer, params = mixer_and_params
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 29, D))
    with jax.default_matmul_precision("highest"):
        want = ref.gdn(MODEL, reference_tensors(params), u)
    assert close(mixer.apply(params, u), want)
    _, _, b, a = mixer._project(params, u)
    beta, _ = mixer._gates(params, b, a)
    assert float(beta.max()) > 1.0 and float(beta.max()) < 2.0


def test_pad_columns_move_nothing_and_steps_follow_the_rule(
        mixer_and_params):
    """Chunks of 5, 16 (11 true columns) and 8 carry state and tail; what
    the pad columns hold does not matter, bit for bit; then single steps
    with an idle lane beside them, against the reference over all 37
    positions; the idle lane's state and tail are left bit for bit."""
    mixer, params = mixer_and_params
    u = jax.random.normal(jax.random.PRNGKey(2), (1, 37, D))
    with jax.default_matmul_precision("highest"):
        want = ref.gdn(MODEL, reference_tensors(params), u)
    state, outs, at = mixer.zero_state(1), [], 0
    for true, bucket in ((5, 8), (11, 16), (8, 8)):
        chunk = jnp.zeros((1, bucket, D)).at[:, :true].set(
            u[:, at:at + true])
        junk = chunk.at[:, true:].set(7.0)
        valid = jnp.arange(bucket) < true
        y, new = mixer.apply_chunk(params, chunk, state, valid)
        _, other = mixer.apply_chunk(params, junk, state, valid)
        for name in new:
            assert (np.asarray(new[name]) == np.asarray(other[name])).all()
        outs.append(y[:, :true])
        state, at = new, at + true
    planted = jax.tree_util.tree_map(lambda s: jnp.full_like(s, 0.5), state)
    both = jax.tree_util.tree_map(lambda a, b: jnp.concatenate([a, b]),
                                  state, planted)
    active = jnp.asarray([True, False])
    for t in range(at, 37):
        y, both = mixer.apply_step(
            params, jnp.concatenate([u[:, t:t + 1]] * 2), both, active)
        outs.append(y[:1])
    assert close(jnp.concatenate(outs, axis=1), want)
    for name in both:
        assert (np.asarray(both[name][1]) == 0.5).all(), name


def test_the_step_form_alone_is_the_rule(mixer_and_params):
    """Every position a tick, from a zero state, two lanes decoding."""
    mixer, params = mixer_and_params
    u = jax.random.normal(jax.random.PRNGKey(4), (2, 12, D))
    with jax.default_matmul_precision("highest"):
        want = ref.gdn(MODEL, reference_tensors(params), u)
    state, outs = mixer.zero_state(2), []
    for t in range(12):
        y, state = mixer.apply_step(params, u[:, t:t + 1], state,
                                    jnp.ones((2,), bool))
        outs.append(y)
    assert close(jnp.concatenate(outs, axis=1), want)


def test_the_state_row_and_the_counts():
    mixer = GatedDeltaNet(D, H, DK, DV, chunk=8)
    assert mixer.state_row() == {"conv": ((3, 2 * H * DK + H * DV),
                                          jnp.float32),
                                 "state": ((H, DK // 2, 2 * DV),
                                           jnp.float32)}
    # a row of 64 is laid two to a stored row of 128 lanes; 128 is not
    assert (mixer.fold, GatedDeltaNet(D, H, DK, 128).fold,
            GatedDeltaNet(D, H, 96, 192).fold,
            GatedDeltaNet(D, H, 8, 12).fold) == (2, 1, 2, 1)
    assert mixer.in_dim == 2 * H * DK + 2 * H * DV + 2 * H
    per_head = 4 * 8 * DK + 2 * 8 * (DK + DV) + 6 * DK * DV + 2 * 8 * DV
    assert mixer.recurrence_flops_per_token() == H * per_head
    # the family counts at the mixer's own tile, 64, whatever a test tiles
    assert ref.CHUNK == GatedDeltaNet(D, H, DK, DV).chunk == 64
    assert ref.recurrence_flops(MODEL) == GatedDeltaNet(
        D, H, DK, DV).recurrence_flops_per_token()


@pytest.mark.parametrize("slots,heads,dk,dv,block", [
    (64, 30, 96, 192, None),    # olmohybrid-7b-pp2's layer, its own block
    (6, 4, 64, 128, None),      # a fold of 1: a row of S is a stored row
    (5, 3, 96, 192, 2),         # the last block cut short
    (7, 2, 16, 64, 3),          # a fold of 2 over 8 stored rows
])
def test_the_tick_kernel_is_the_xla_form(slots, heads, dk, dv, block):
    """The Pallas tick (``ops.pallas_kernels.delta_rule_step``, interpreted
    here) against the XLA form it replaces (``GatedDeltaNet._update``):
    random states, unit keys, ``beta`` in (0, 2), log decays down to
    ``-e^0.5``, a random set of idle streams.  The new state and ``o``
    within ``TIGHT`` of the largest value; an idle stream's state bit for
    bit."""
    from neural_networks_parallel_training_with_mpi_tpu.ops import (
        pallas_kernels as pk,
    )

    mixer = GatedDeltaNet(heads * dk, heads, dk, dv)
    f = mixer.fold
    assert f == (1 if dv == 128 else 2)
    ks = jax.random.split(jax.random.PRNGKey(slots * heads + dk), 7)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    state = jax.random.normal(ks[0], (slots, heads, dk // f, f * dv))
    k = unit(jax.random.normal(ks[1], (slots, heads, dk)))
    q = unit(jax.random.normal(ks[2], (slots, heads, dk))) * dk ** -0.5
    v = jax.random.normal(ks[3], (slots, heads, dv))
    beta = jax.random.uniform(ks[4], (slots, heads), minval=0.0, maxval=2.0)
    g = -jnp.exp(jax.random.uniform(ks[5], (slots, heads), minval=-3.0,
                                    maxval=0.5))
    active = jax.random.bernoulli(ks[6], 0.7, (slots,)).at[0].set(False)
    assert bool(active.any())
    want_s, want_o = mixer._update(state, k, q, v, beta, g, active)
    got_s, got_o = pk.delta_rule_step(state, k, q, v, beta, jnp.exp(g),
                                      active, block=block, interpret=True)
    assert got_s.shape == state.shape and got_o.shape == (slots, heads, dv)
    assert close(got_s, want_s) and close(got_o, want_o)
    idle = ~np.asarray(active)
    assert (np.asarray(got_s)[idle] == np.asarray(state)[idle]).all()
    assert not (np.asarray(got_s)[~idle] == np.asarray(state)[~idle]).all()
