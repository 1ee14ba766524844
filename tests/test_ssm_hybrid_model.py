"""A Mamba-2 mixer beside grouped-query attention in every block, with the
model's fixed multipliers (``models/ssm.py``, ``models/transformer.py``), each
against the plain float32 reference of
``benchmark/families/ssm_attn_parallel.py`` (which imports nothing from the
program and runs the recurrence one step a token), at a toy size on the CPU.

Everything here is float32 on both sides.  The program's chunked recurrence
and the reference's step-by-step one are the same sums in another order: inside
a tile the program forms ``exp(cs_q - cs_s)`` from a running sum of log decays
where the reference multiplies the decays one by one, and it adds a tile's
products before it adds the entering state's.  ``TIGHT`` = 1e-5 of the
tensor's scale covers that (observed at most 2e-6) and nothing else: a
multiplier left out moves a branch by tens of percent, and each of the nine,
the gate and the skip ``D`` is shown to fail it.
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

from benchmark.families import ssm_attn_parallel as ref           # noqa: E402
from neural_networks_parallel_training_with_mpi_tpu.models import (  # noqa: E402
    Transformer, TransformerConfig,
)
from neural_networks_parallel_training_with_mpi_tpu.models.generate import (  # noqa: E402
    init_kv_cache,
)
from neural_networks_parallel_training_with_mpi_tpu.serve import (  # noqa: E402
    PagedDecodeServer,
)

TIGHT = 1e-5

# the toy: every mechanism of the real configuration, small, and every
# multiplier away from 1 (the published attention_in_multiplier is 1, which a
# test could not tell from its absence).  Tiles of 8, so that the lengths
# below are and are not whole tiles
MODEL = {"vocab_size": 96, "d_model": 48, "n_layers": 3, "n_heads": 4,
         "n_kv_heads": 2, "head_dim": 16, "d_ff": 80, "d_ssm": 48,
         "ssm_heads": 4, "ssm_head_dim": 12, "ssm_state": 16,
         "ssm_groups": 2, "ssm_conv": 4, "ssm_chunk": 8, "max_seq_len": 128,
         "rms_eps": 1e-5, "rope_theta": 1e11,
         "embedding_multiplier": 5.657, "lm_head_multiplier": 0.0625,
         "attention_in_multiplier": 0.8, "attention_out_multiplier": 0.3,
         "key_multiplier": 0.11, "ssm_in_multiplier": 0.25,
         "ssm_out_multiplier": 0.35,
         "ssm_multipliers": [0.354, 0.25, 0.177, 0.5, 0.354],
         "mlp_multipliers": [0.177, 0.4], "param_dtype": "float32",
         "compute_dtype": "float32", "family": ref, "config": "toy"}


def close(a, b, tol=TIGHT):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-30)


def tensors(model=MODEL, seed=5):
    from benchmark.harness import weights

    maker = weights.Maker(model, seed)
    return maker.outer(), maker.layers()


@pytest.fixture(scope="module")
def toy():
    outer, layers = tensors()
    net = ref.program_model(MODEL)
    return net, ref.to_program(MODEL, outer, layers), outer, layers


def reference_logits(outer, layers, ids, model=MODEL):
    with jax.default_matmul_precision("highest"):
        x = ref.embed(model, outer, ids)
        for i, p in enumerate(layers):
            x = ref.block(model, p, x, i)
        return ref.head_logits(model, outer, x)


IDS = np.random.default_rng(3).integers(0, 96, size=(2, 37))


# ---- (a) the mixer, three forms of one recurrence ---------------------------

@pytest.mark.parametrize("length", [8, 24, 13, 37])
def test_chunked_recurrence_is_the_step_by_step_one(toy, length):
    """The mixer over a sequence (tiles of 8) against the reference's
    recurrence, one step a token, at lengths that are whole tiles and at
    lengths that are not."""
    net, params, _outer, layers = toy
    mixer = net._block_modules()["ssm"]
    assert (mixer.chunk, mixer.d_ssm, mixer.conv_dim, mixer.in_dim) == (
        8, 48, 48 + 2 * 32, 48 + 112 + 4)
    u = jax.random.normal(jax.random.PRNGKey(length), (2, length, 48))
    with jax.default_matmul_precision("highest"):
        want = ref.mixer(MODEL, layers[1], u)
    assert close(mixer.apply(params["blocks"][1]["ssm"], u), want)


def test_a_carried_state_and_single_steps_give_the_same_sequence(toy):
    """Chunks of 5, 16 (11 true columns) and 8 that carry state and tail,
    then 10 single steps with an idle lane beside them, against the
    reference over all 37 positions; pad columns and the idle lane leave
    state and tail bit for bit."""
    net, params, _outer, layers = toy
    mixer, p = net._block_modules()["ssm"], params["blocks"][1]["ssm"]
    u = jax.random.normal(jax.random.PRNGKey(1), (1, 37, 48))
    with jax.default_matmul_precision("highest"):
        want = ref.mixer(MODEL, layers[1], u)
    state, outs, at = mixer.zero_state(1), [], 0
    for true, bucket in ((5, 8), (11, 16), (8, 8)):
        chunk = jnp.zeros((1, bucket, 48)).at[:, :true].set(
            u[:, at:at + true])
        # what the pad columns hold must not matter
        junk = chunk.at[:, true:].set(7.0)
        valid = jnp.arange(bucket) < true
        y, new = mixer.apply_chunk(p, chunk, state, valid)
        _, other = mixer.apply_chunk(p, junk, state, valid)
        for name in new:
            assert (np.asarray(new[name]) == np.asarray(other[name])).all()
        outs.append(y[:, :true])
        state, at = new, at + true
    # two lanes: lane 0 decodes, lane 1 idles on a planted state
    planted = jax.tree_util.tree_map(lambda s: jnp.full_like(s, 0.5), state)
    both = jax.tree_util.tree_map(lambda a, b: jnp.concatenate([a, b]),
                                  state, planted)
    active = jnp.asarray([True, False])
    for t in range(at, 37):
        y, both = mixer.apply_step(
            p, jnp.concatenate([u[:, t:t + 1]] * 2), both, active)
        outs.append(y[:1])
    assert close(jnp.concatenate(outs, axis=1), want)
    for name in both:
        assert (np.asarray(both[name][1]) == 0.5).all(), name


# ---- (b) the whole toy model ------------------------------------------------

def test_the_whole_model_against_the_reference(toy):
    net, params, outer, layers = toy
    want = reference_logits(outer, layers, IDS)
    mine = net.apply(params, jnp.asarray(IDS))
    assert mine.shape == (2, 37, 96) and close(mine, want)
    # the training forward has a gradient (no cell trains it)
    loss = lambda p: net.apply(p, jnp.asarray(IDS[:1, :13])).var()  # noqa: E731
    grads = jax.grad(loss)(params)
    norms = jax.tree_util.tree_map(lambda g: float(jnp.abs(g).max()),
                                   grads["blocks"][0]["ssm"])
    assert all(np.isfinite(v) and v > 0
               for v in jax.tree_util.tree_leaves(norms)), norms


# ---- (c) each multiplier, the gate and the skip are held by the tolerance ---

def _neutral(name, index=None):
    def change(model, layers, monkeypatch):
        if name == "gate":
            monkeypatch.setattr(ref, "gate", lambda y, z: y)
        elif name == "D":
            layers = [{**p, "D": jnp.zeros_like(p["D"])} for p in layers]
        elif index is None:
            model = {**model, name: 1.0}
        else:
            value = list(model[name])
            value[index] = 1.0
            model = {**model, name: value}
        return model, layers
    return change


LEFT_OUT = ([(n, None) for n in (
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier")]
    + [("ssm_multipliers", i) for i in range(5)]
    + [("mlp_multipliers", i) for i in range(2)]
    + [("gate", None), ("D", None)])


@pytest.mark.parametrize("name,index", LEFT_OUT,
                         ids=[n if i is None else f"{n}[{i}]"
                              for n, i in LEFT_OUT])
def test_a_reference_without_one_piece_is_another_model(toy, monkeypatch,
                                                        name, index):
    """Each of the nine multipliers (the mixer's five and the feed-forward's
    two entry by entry), the gate and the skip ``D`` set to its neutral
    value in the REFERENCE: the program no longer agrees with it, by a
    hundred times the tolerance.  So the comparison of (b) holds every one
    of them in the program."""
    net, params, outer, layers = toy
    model, changed = _neutral(name, index)(MODEL, layers, monkeypatch)
    other = reference_logits(outer, changed, IDS[:1], model)
    mine = net.apply(params, jnp.asarray(IDS[:1]))
    assert not close(mine, other, 100 * TIGHT)


# ---- (d) refusals by name ---------------------------------------------------

def test_paths_that_cannot_run_the_block_refuse_it_by_name(toy):
    net, params, _outer, _layers = toy
    with pytest.raises(ValueError, match="recurrent state.*ssm_heads"):
        net.cfg.require_plain_block("the dense KV cache")
    with pytest.raises(ValueError, match="recurrent state.*ssm_heads"):
        init_kv_cache(net, 1, 16)
    plain = TransformerConfig(key_multiplier=0.5)
    with pytest.raises(ValueError, match="multipliers"):
        plain.require_plain_block("the pipeline step")
    TransformerConfig().require_plain_block("anything")     # today's models
    with pytest.raises(ValueError, match="whole sequence"):
        TransformerConfig(**{**net.cfg.__dict__, "attention": "ring"})
    with pytest.raises(ValueError, match="attention_kind"):
        TransformerConfig(ssm_heads=2, ssm_head_dim=4, ssm_state=4,
                          attention_kind="mla", pos_encoding="rope",
                          q_lora_rank=4, kv_lora_rank=4, qk_nope_head_dim=4,
                          qk_rope_head_dim=4, v_head_dim=4)
    kw = dict(slots=2, num_blocks=16, block_size=4, max_len=32)
    for refused in (dict(prefix_cache=True), dict(kv_quant=True)):
        with pytest.raises(ValueError, match="recurrent state.*ssm_heads"):
            PagedDecodeServer(net, params, **kw, **refused)
    srv = PagedDecodeServer(net, params, **kw)
    rid = srv.try_admit([1, 2, 3], 2)
    srv.prefill_step(rid, 8)
    with pytest.raises(ValueError, match="export_stream.*recurrent state"):
        srv.export_stream(rid)
    with pytest.raises(ValueError, match="import_stream.*recurrent state"):
        srv.import_stream({})


def test_the_state_row_and_the_counts(toy):
    """The server is told the per-stream shapes and types; the model counts
    the mixer's products in ``fwd_flops``."""
    net, _params, _outer, _layers = toy
    assert net.state_row() == {"conv": ((3, 112), jnp.float32),
                               "ssm": ((4, 12, 16), jnp.float32)}
    assert Transformer(TransformerConfig()).state_row() == {}
    plain = Transformer(TransformerConfig(**{
        **net.cfg.__dict__, "ssm_heads": 0}))
    per_token = (2 * 48 * (164 + 48)                 # the two projections
                 + 2 * 8 * 16 * 2 + 4 * (2 * 8 * 12 + 4 * 12 * 16))
    assert net.fwd_flops((2, 10)) - plain.fwd_flops((2, 10)) \
        == 3 * 20 * per_token
    assert ref.recurrence_flops(MODEL) == per_token - 2 * 48 * 212


def test_rows_landing_a_program_behind_serve_the_same_tokens(
        toy, staggered_batch):
    """Recurrent state beside the pages under the request boundary of ISSUE
    36: the admission's one program zeroes the slot's state rows in place
    while the last stream's row is still in flight, and every request's
    tokens are those it gets alone on a fresh scheduler; no state row is
    left held."""
    net, params = toy[0], toy[1]
    rng = np.random.default_rng(4)
    requests = [(rng.integers(0, 96, size=int(rng.integers(2, 30))).tolist(),
                 int(rng.integers(2, 12))) for _ in range(7)]
    sched = staggered_batch(net, params, requests, 2, slots=2, num_blocks=33,
                            block_size=8, max_len=64, prefill_chunk=8)
    assert sched.ssm_counters == sched.server.ssm_counters
    assert sched.ssm_counters["ssm_prefill_tokens"] == 3 * sum(
        len(p) for p, _ in requests)
