"""A rotation chosen per kind of layer (plain rotary on window layers, YaRN on
full ones), the softmax router over every expert held, no shared expert,
against the plain float32 reference of
``benchmark/families/gqa_window_yarn_moe.py`` (which imports nothing from the
program), at a toy size on the CPU: window 8, chunks of 8, pages of 4, 8
experts top-2, YaRN of factor 4 over an original 16 positions, and streams
that run past both.

Everything here is float32 on both sides, so program and reference differ in
the order of float32 sums only.  ``TIGHT`` = 2e-5 of the tensor's scale covers
that and nothing else: each planted fault (no ``attention_factor``, YaRN on the
window layers, plain rotary on the full ones, a window off by one) is shown to
break it.
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

from benchmark.families import gqa_window_yarn_moe as ref          # noqa: E402
from neural_networks_parallel_training_with_mpi_tpu.models import (  # noqa: E402
    Transformer, TransformerConfig, moe,
)
from neural_networks_parallel_training_with_mpi_tpu.ops.rope import (  # noqa: E402
    RopeScaling, Rotary,
)
from neural_networks_parallel_training_with_mpi_tpu.serve import (  # noqa: E402
    Scheduler, ServeConfig, paged_kv,
)

TIGHT = 2e-5

# the toy: every mechanism of the real configuration, small; the two kinds
# rotate at thetas of their own, so that a rotation taken from the wrong
# kind shows
YARN = {"rope_type": "yarn", "rope_theta": 10, "factor": 4,
        "original_max_position_embeddings": 16, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.1386294361119891}
KINDS = [ref.WINDOW, ref.WINDOW, ref.WINDOW, ref.FULL]
MODEL = {"vocab_size": 96, "d_model": 48, "n_layers": 4, "n_heads": 4,
         "n_kv_heads": 2, "head_dim": 16, "layer_types": KINDS * 2,
         "mlp_layer_types": [ref.SPARSE] * 8, "sliding_window": 8,
         "dense_ff": 72, "expert_ff": 24, "shared_experts": 0,
         "experts_total": 8, "experts_first": 0, "experts_held": 8,
         "top_k": 2, "max_seq_len": 128, "rms_eps": 1e-6,
         "rope": {ref.WINDOW: {"rope_type": "default", "rope_theta": 1000},
                  ref.FULL: YARN},
         "param_dtype": "float32", "compute_dtype": "float32",
         "family": ref, "config": "toy"}
SERVE = dict(slots=2, num_blocks=48, block_size=4, max_len=80,
             prefill_chunk=8)


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


def faulty(net, fault):
    """The toy's program config with one planted fault."""
    window, full = net.cfg.rope_kinds
    plain_full = Rotary(full.theta)
    kw = {"no_attention_factor": {"rope_kinds": (window, Rotary(
              full.theta, RopeScaling(**{**full.scaling.__dict__,
                                         "attention_factor": 1.0})))},
          "yarn_on_window": {"rope_kinds": (Rotary(window.theta,
                                                   full.scaling), full)},
          "plain_on_full": {"rope_kinds": (window, plain_full)},
          "window_7": {"sliding_window": 7},
          "window_9": {"sliding_window": 9}}[fault]
    return Transformer(TransformerConfig(**{**net.cfg.__dict__, **kw}))


# ---- the model -------------------------------------------------------------

def test_each_kind_of_layer_rotates_as_its_config_says(toy):
    net = toy[0]
    c = net.cfg
    assert [c.layer_window(i) for i in range(4)] == [8, 8, 8, None]
    assert [c.layer_rotary(i) for i in range(4)] == [Rotary(1000.0)] * 3 + [
        Rotary(10.0, RopeScaling(factor=4.0,
                                 original_max_position_embeddings=16,
                                 beta_fast=32.0, beta_slow=1.0,
                                 attention_factor=1.1386294361119891))]
    moe_layer = net._block_modules(0)["moe"]
    assert (moe_layer.score, moe_layer.span, moe_layer.shared_ff,
            moe_layer.routed_scale) == ("softmax", (0, 8), 0, 1.0)


def test_apply_matches_the_reference(toy):
    """The full forward over 40 positions (five windows, past the original
    16 of the YaRN layer) against the reference's."""
    net, params, outer, layers = toy
    ids = np.random.default_rng(0).integers(0, 96, size=(2, 40))
    want = reference_logits(outer, layers, ids)
    assert close(net.apply(params, jnp.asarray(ids)), want)


@pytest.mark.parametrize("fault", ["no_attention_factor", "yarn_on_window",
                                   "plain_on_full", "window_7", "window_9"])
def test_each_planted_fault_breaks_the_tolerance(toy, fault):
    net, params, outer, layers = toy
    ids = np.random.default_rng(0).integers(0, 96, size=(2, 40))
    want = reference_logits(outer, layers, ids)
    got = faulty(net, fault).apply(params, jnp.asarray(ids))
    assert not close(got, want, 100 * TIGHT)


# ---- the served path -------------------------------------------------------

def served_logits_gap(net, params, outer, layers, prompts, news, **cfg):
    """Serve through ``Scheduler`` and return the widest gap between the
    reference's best logit and its logit of each served token, over the
    reference's logit scale: 0 where every served token is the reference's
    own greedy choice."""
    sched = Scheduler(net, params, ServeConfig(**cfg))
    rids = [sched.submit(p, n) for p, n in zip(prompts, news)]
    sched.run_until_drained()
    worst = 0.0
    for rid, p in zip(rids, prompts):
        toks = sched.result(rid)
        logits = np.asarray(reference_logits(outer, layers,
                                             np.asarray([toks])))[0]
        for t in range(len(p), len(toks)):
            row = logits[t - 1]
            worst = max(worst, float(row.max() - row[toks[t]])
                        / float(np.abs(row).max()))
    sched.server.assert_drained()
    sched.close()
    return worst


PROMPTS = [np.random.default_rng(1).integers(0, 96, size=n).tolist()
           for n in (50, 9, 23)]


@pytest.mark.parametrize("impl", ["gathered", "fused"])
def test_chunked_prefill_then_decode_match_the_full_forward(toy, impl):
    """Prefill in chunks of 8 (as long as the window: a window layer's chunk
    reads the keys the chunk before it wrote, through the window kind's
    table) and decode through both kinds of cache, a stream of 70 positions,
    against the reference's one full forward pass: every served token is the
    reference's greedy choice to within float32 noise."""
    net, params, outer, layers = toy
    gap = served_logits_gap(net, params, outer, layers, PROMPTS,
                            (20, 12, 7), attn_impl=impl, **SERVE)
    assert gap <= TIGHT


@pytest.mark.parametrize("fault", ["plain_on_full", "window_9"])
def test_the_served_path_fails_a_planted_fault(toy, fault):
    net, params, outer, layers = toy
    gap = served_logits_gap(faulty(net, fault), params, outer, layers,
                            PROMPTS, (20, 12, 7), **SERVE)
    assert gap > 100 * TIGHT


# ---- the share -------------------------------------------------------------

def test_two_halves_of_the_experts_add_up_to_the_uncut_layer():
    """The share test at toy size: the routed parts of experts 0-3 and 4-7
    add up to what the uncut reference layer gives with all 8 (no shared
    expert to count once), all 8 held is the uncut layer itself, and the
    program's layer told which experts it holds computes each part."""
    from benchmark.harness import weights

    p = {k: v.astype(jnp.float32)
         for k, v in weights.Maker(MODEL, 11).layer(1).items()}
    y = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 48), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut = ref.routed(MODEL, p, y)
        parts = jnp.zeros_like(y)
        for first, held in ((0, 4), (4, 4), (0, 8)):
            share = {**MODEL, "experts_first": first, "experts_held": held}
            mine = {**p, **{n: p[n][first:first + held]
                            for n in ref.base.EXPERTS}}
            part = ref.routed(share, mine, y)
            layer = moe.DroplessMoE(48, 24, 8, top_k=2, held=(first, held))
            got, _aux = layer.apply(ref.to_program_layer(share, mine, 1)
                                    ["moe"], y)
            assert close(got, part), (first, held)
            if held == 4:
                parts = parts + part
            else:
                assert close(part, uncut)
    assert close(parts, uncut)
    # softmax over all 8, top-2 renormalised: each token's weights sum to 1
    w = ref.combine_weights(MODEL, p, y)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-6)
    assert int((np.asarray(w) > 0).sum(-1).max()) == 2


# ---- the prefill chunks' pairs ---------------------------------------------

@pytest.mark.parametrize("start,width", [(0, 8), (3, 8), (8, 8), (13, 5),
                                         (30, 1), (0, 1)])
def test_chunk_pairs_count_what_the_chunk_scores(start, width):
    windows = (8, 8, None, 8, None)
    full = sum(p + 1 for p in range(start, start + width))
    win = sum(min(p + 1, 8) for p in range(start, start + width))
    assert paged_kv.chunk_pairs(start, width, windows) == (2 * full, 3 * win)


def test_the_retire_spans_and_records_carry_the_pairs(toy, tmp_path):
    """Every chunk of a served prompt is counted once, by kind, and the
    ``retire`` span that lands the stream is stamped with it (what the
    benchmark's ``prefill_attention_roofline.serve`` reads)."""
    import json
    import subprocess

    from neural_networks_parallel_training_with_mpi_tpu.train import (
        trace as trace_lib,
    )

    net, params = toy[0], toy[1]
    spans = []
    listener = lambda n, t, d, a: spans.append((n, dict(a or {})))  # noqa: E731
    tracer = trace_lib.start_run(str(tmp_path / "trace"))
    trace_lib.add_listener(listener)
    sched = Scheduler(net, params, ServeConfig(
        telemetry_dir=str(tmp_path), metrics_every=1, **SERVE))
    try:
        for n in (30, 12):
            sched.submit(list(range(1, n + 1)), 5)
        sched.run_until_drained()
    finally:
        sched.close()
        trace_lib.remove_listener(listener)
        trace_lib.stop_run(tracer)
    full = sum(p + 1 for n in (30, 12) for p in range(n))
    win = sum(min(p + 1, 8) for n in (30, 12) for p in range(n))
    want = {"prefill_full_pairs": full, "prefill_window_pairs": 3 * win}
    assert sched.pair_counters == want
    stamped = [a for n, a in spans if n == "retire"
               and "prefill_full_pairs" in a]
    assert stamped and all(stamped[-1][k] == v for k, v in want.items())
    final = [r for r in map(json.loads, open(tmp_path / "metrics.jsonl"))
             if r.get("kind") == "serve" and r.get("final")][-1]
    assert {k: final[k] for k in want} == want
    out = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "metrics_summary.py"),
         str(tmp_path)], capture_output=True, text=True, check=True).stdout
    assert f"pairs by kind of layer: {full} full / {3 * win} window" in out


def test_a_model_of_one_kind_counts_every_layer_as_full():
    net = Transformer(TransformerConfig(
        vocab_size=64, max_seq_len=64, n_layers=3, d_model=32, n_heads=4,
        d_ff=64, pos_encoding="rope"))
    params = net.init(jax.random.PRNGKey(0))
    srv = paged_kv.PagedDecodeServer(net, params, slots=1, num_blocks=16,
                                     block_size=4, max_len=32)
    rid = srv.try_admit(list(range(1, 11)), 3)
    while not srv.prefill_step(rid, 4):
        pass
    while not srv.done(rid):
        srv.step()
    srv.land(every=True)
    assert srv.pair_counters == {"prefill_full_pairs": 3 * 55,
                                 "prefill_window_pairs": 0}


# ---- what cannot run the block says so -------------------------------------

def test_paths_without_yarn_refuse_a_rotation_by_kind():
    """A model of one kind whose every layer is YaRN-rotated: the dense
    cache's generator rotates plain, so it refuses the block by name."""
    import importlib

    generate = importlib.import_module(
        "neural_networks_parallel_training_with_mpi_tpu.models.generate")
    yarn = Rotary(10.0, RopeScaling(factor=4.0,
                                    original_max_position_embeddings=16))
    net = Transformer(TransformerConfig(
        vocab_size=64, max_seq_len=64, n_layers=2, d_model=32, n_heads=4,
        d_ff=64, pos_encoding="rope", rope_kinds=(yarn, yarn)))
    with pytest.raises(ValueError, match="rotation by kind of layer"):
        generate.init_kv_cache(net, 1, 16)
    with pytest.raises(ValueError, match="pos_encoding must be 'rope'"):
        TransformerConfig(rope_kinds=(yarn, yarn))


# ---- the programs' lowered text --------------------------------------------

# the toy's training step and paged programs as commit 61f07d9 lowered them
# (StableHLO, no locations), before the paged programs ran the model's own
# block with the cache's cores passed in: that move was to change no
# operation and no order of operations (on the chip the programs'
# compile-cache keys then stay).  A change that means to move them re-pins
# these.
_TEXT_SHA256 = {
    "train": "3bfdb51aa92665b829c2b23c63a2d016f68e5c1c7b5db195d5a87913ad3aa9f9",
    "decode": "c11b28ef8e860506b08851c80e5e2eb51bda3354bc2c493153b44c2b6fe306e3",
    "prefill": "385090e13eb6143969d981f40491dd8abf3c2bc0c03a95d89bbbdf986d6da870",
}


@pytest.fixture(scope="module")
def lowered(toy, lowered_programs):
    return lowered_programs(toy[0], **SERVE)


@pytest.mark.parametrize("program", sorted(_TEXT_SHA256))
def test_the_programs_lower_to_the_parents_text(lowered, program):
    import hashlib

    text = lowered[program].as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == _TEXT_SHA256[program]
