"""Window and full attention layers in one model, the per-head norm of q and
k, full layers without positions, a dense leading layer and the sigmoid
router, each against the plain float32 reference of
``benchmark/families/gqa_window_moe.py`` (which imports nothing from the
program), at a toy size on the CPU.

Everything here is float32 on both sides, so program and reference differ in
the order of float32 sums only.  ``TIGHT`` = 2e-5 of the tensor's scale covers
that (observed at most 4e-6) and nothing else: a router computed in bfloat16
moves a combine weight by about 1e-2, a window off by one takes a key's whole
share of a softmax away or adds one (about 1e-2 of the scale), a rotated full
layer changes every score, and each is shown to fail it.
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

from benchmark.families import gqa_window_moe as ref             # noqa: E402
from neural_networks_parallel_training_with_mpi_tpu.models import (  # noqa: E402
    Transformer, TransformerConfig, moe,
)
from neural_networks_parallel_training_with_mpi_tpu.parallel import (  # noqa: E402
    sequence,
)
from neural_networks_parallel_training_with_mpi_tpu.serve import (  # noqa: E402
    PagedDecodeServer, Scheduler, ServeConfig,
)

TIGHT = 2e-5

# the toy: every mechanism of the real configuration, small.  Window 8 over
# blocks of 4, so that a stream of 70 positions is several windows long and
# a chunk of 16 is longer than the window
KINDS = [ref.WINDOW, ref.WINDOW, ref.WINDOW, ref.FULL, ref.WINDOW]
MODEL = {"vocab_size": 96, "d_model": 48, "n_layers": 5, "n_heads": 8,
         "n_kv_heads": 2, "head_dim": 8, "layer_types": KINDS * 2,
         "mlp_layer_types": [ref.DENSE] + [ref.SPARSE] * 9,
         "sliding_window": 8, "dense_ff": 72, "expert_ff": 24,
         "shared_experts": 1, "experts_total": 16, "experts_first": 4,
         "experts_held": 4, "top_k": 4, "routed_scale": 2.5,
         "router_bias_std": 0.02, "max_seq_len": 128, "rms_eps": 1e-5,
         "rope_theta": 10000, "param_dtype": "float32",
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


def program_attention(net, blk, x, layer):
    """``x + Attn(n1(x))`` of the program's training block, layer ``layer``:
    the block with its feed-forward's output weights at zero."""
    blk = jax.tree_util.tree_map(lambda a: a, blk)
    if "moe" in blk:
        blk["moe"] = {**blk["moe"],
                      "experts": {**blk["moe"]["experts"], "w_out": jnp.
                                  zeros_like(blk["moe"]["experts"]["w_out"])},
                      "shared": {**blk["moe"]["shared"], "w_out": jnp.
                                 zeros_like(blk["moe"]["shared"]["w_out"])}}
    else:
        blk["ff_out"] = {"w": jnp.zeros_like(blk["ff_out"]["w"])}
    return net._block(blk, x, layer=layer)[0]


# ---- the pieces ----------------------------------------------------------

@pytest.mark.parametrize("layer,kind", [(0, "window"), (3, "full")])
def test_attention_with_and_without_window_and_rotation(toy, layer, kind):
    """A window layer rotates q and k and sees 8 keys; a full layer does
    not rotate and sees them all: both against the reference, and each
    shown to differ from the other kind's arithmetic."""
    net, params, _outer, layers = toy
    assert (net.cfg.layer_window(layer), net.cfg.layer_rotary(layer)) == (
        (8, True) if kind == "window" else (None, False))
    x = jax.random.normal(jax.random.PRNGKey(layer), (2, 40, 48))
    with jax.default_matmul_precision("highest"):
        want = ref.attention_half(MODEL, layers[layer], x, layer)
        other = ref.attention_half(MODEL, layers[layer], x, 3 - layer)
    mine = program_attention(net, params["blocks"][layer], x, layer)
    assert close(mine, want)
    assert not close(mine, other, 1e-3)     # the kind is not decoration
    # the head width is its own: q is 64 wide where d_model is 48
    assert net.cfg.head_dim == 8 and net.cfg.q_dim == 64
    assert params["blocks"][layer]["qkv"]["w"].shape == (48, 64 + 2 * 16)
    assert params["blocks"][layer]["attn_out"]["w"].shape == (64, 48)


def test_a_window_off_by_one_and_a_rotated_full_layer_fail(toy):
    net, params, _outer, layers = toy
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 40, 48))
    with jax.default_matmul_precision("highest"):
        want = ref.attention_half(MODEL, layers[0], x, 0)
        full = ref.attention_half(MODEL, layers[3], x, 3)
    for window in (7, 9):       # 8 is the model's: itself and 7 before it
        off = Transformer(TransformerConfig(**{
            **net.cfg.__dict__, "sliding_window": window}))
        assert not close(program_attention(off, params["blocks"][0], x, 0),
                         want)
    rotated = Transformer(TransformerConfig(**{
        **net.cfg.__dict__, "rope_global": True}))
    assert not close(program_attention(rotated, params["blocks"][3], x, 3),
                     full)
    assert close(program_attention(net, params["blocks"][3], x, 3), full)


def test_the_per_head_norm(toy):
    """One scale vector of ``head_dim`` for all heads, statistics over the
    lanes of each head, q and k alike, before the rotation."""
    net, params, _outer, layers = toy
    blk, p = params["blocks"][1], layers[1]
    assert blk["q_norm"]["scale"].shape == blk["k_norm"]["scale"].shape == (8,)
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 8, 8)) * 3 + 1
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 2, 8)) * 2 - 1
    mods = net._block_modules(1)
    qn, kn = net.qk_normed(mods, blk, q, k)
    assert close(qn, ref.rms_norm(q, p["q_norm.scale"], 1e-5))
    assert close(kn, ref.rms_norm(k, p["k_norm.scale"], 1e-5))
    # without it the block is another block
    bare = Transformer(TransformerConfig(**{**net.cfg.__dict__,
                                            "qk_norm": False}))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 12, 48))
    assert not close(program_attention(bare, blk, x, 1),
                     program_attention(net, blk, x, 1), 1e-3)


def test_sigmoid_router_choices_and_weights(toy):
    """Scores in float32 over all 16 experts, the 4 largest of score + bias
    chosen, the chosen SCORES renormalised and scaled by 2.5: the program's
    sorted dispatch against the reference's weights on the held experts, and
    a bias that flips a choice flips it in both."""
    net, params, _outer, layers = toy
    layer = net._block_modules(1)["moe"]
    assert (layer.score, layer.routed_scale, layer.span) == (
        "sigmoid", 2.5, (4, 4))
    p, gate = dict(layers[1]), dict(params["blocks"][1]["moe"]["gate"])
    y = jax.random.normal(jax.random.PRNGKey(3), (64, 48))

    def both(p, gate):
        with jax.default_matmul_precision("highest"):
            want = ref.combine_weights(MODEL, p, y[None])[0]    # (N, held)
            _w, want_i = ref.router_choice(MODEL, p, y[None])
        _order, sizes, weight, _probs, top_i = layer.route(gate, y, None)
        mine = jnp.zeros((64, 4)).at[
            jnp.arange(64)[:, None], jnp.clip(top_i - 4, 0, 3)].add(
                jnp.where((top_i >= 4) & (top_i < 8), weight, 0.0))
        assert (np.sort(np.asarray(top_i), -1)
                == np.sort(np.asarray(want_i[0]), -1)).all()
        assert int(sizes.sum()) == int((np.asarray(want) > 0).sum())
        assert close(mine, want)
        return np.asarray(top_i), np.asarray(want)

    chosen, w = both(p, gate)
    # every token's weights over ALL experts sum to 2.5; here the held part
    assert w.sum(-1).max() <= 2.5 + 1e-5 and (w >= 0).all()
    # the bias moved some choices and not most
    no_bias = {**gate, "bias": jnp.zeros_like(gate["bias"])}
    plain = np.asarray(layer.route(no_bias, y, None)[4])
    moved = np.mean([len(set(a) - set(b)) for a, b in zip(chosen, plain)]) / 4
    assert 0.0 < moved < 0.5
    # a bias large enough to force expert 5 into every token's choice: in
    # both, and its weight is still its SCORE's share (under 2.5), not the
    # bias's
    forced = p["router.bias"].at[5].set(10.0)
    chosen, w = both({**p, "router.bias": forced}, {**gate, "bias": forced})
    assert (chosen == 5).any(-1).all() and w[:, 1].max() < 2.5
    # a router computed in bfloat16 is another router
    low = {"w": gate["w"].astype(jnp.bfloat16).astype(jnp.float32),
           "bias": gate["bias"]}
    lw = layer.route(low, y.astype(jnp.bfloat16).astype(jnp.float32),
                     None)[2]
    assert not close(lw, layer.route(gate, y, None)[2])


def test_the_dense_layer_beside_the_sparse_ones(toy):
    net, params, _outer, layers = toy
    assert [net.cfg.layer_is_moe(i) for i in range(5)] == [
        False, True, True, True, True]
    assert "moe" not in params["blocks"][0]
    assert params["blocks"][0]["ff_in"]["w"].shape == (48, 72)
    assert params["blocks"][1]["moe"]["experts"]["w_in"].shape == (4, 48, 24)
    assert params["blocks"][1]["moe"]["gate"]["bias"].shape == (16,)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 48))
    for i in (0, 1, 3):
        with jax.default_matmul_precision("highest"):
            want = ref.block(MODEL, layers[i], x, i)
        assert close(net._block(params["blocks"][i], x, layer=i)[0], want), i
    # the program's own init builds the same tree
    own = net.init(jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(own) \
        == jax.tree_util.tree_structure(params)
    assert net.fwd_flops((1, 16)) > 0


def test_apply_matches_the_reference(toy):
    net, params, outer, layers = toy
    ids = np.random.default_rng(0).integers(0, 96, size=(2, 48))
    want = reference_logits(outer, layers, ids)
    assert close(net.apply(params, jnp.asarray(ids)), want)
    # a traced layer index walks the same layers (how reference/serve.py
    # calls a run of layers of one shape)
    with jax.default_matmul_precision("highest"):
        x = ref.embed(MODEL, outer, ids)
        traced = jax.jit(lambda p, x, i: ref.block(MODEL, p, x, i))
        for i, p in enumerate(layers):
            x = traced(p, x, i)
        assert close(ref.head_logits(MODEL, outer, x), want, 1e-6)


# ---- the served path -----------------------------------------------------

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


@pytest.mark.parametrize("impl", ["gathered", "fused"])
def test_chunked_prefill_then_decode_match_the_full_forward(toy, impl):
    """Prefill in chunks of 16 (longer than the window of 8) and decode
    through both kinds of cache, a stream of 70 positions (almost nine
    windows), against the reference's one full forward pass: every served
    token is the reference's greedy choice to within float32 noise.  Greedy
    ties aside a wrong key anywhere moves a logit by 1e-2 of the scale."""
    net, params, outer, layers = toy
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 96, size=n).tolist() for n in (50, 9, 23)]
    gap = served_logits_gap(net, params, outer, layers, prompts,
                            (20, 12, 7), slots=2, num_blocks=40,
                            block_size=4, max_len=80, prefill_chunk=16,
                            attn_impl=impl)
    assert gap <= TIGHT


def test_the_served_path_fails_a_wrong_window(toy):
    """The same traffic through a server whose model sees 9 keys is not the
    reference's."""
    net, params, outer, layers = toy
    off = Transformer(TransformerConfig(**{**net.cfg.__dict__,
                                           "sliding_window": 9}))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 96, size=n).tolist() for n in (50, 9, 23)]
    gap = served_logits_gap(off, params, outer, layers, prompts,
                            (20, 12, 7), slots=2, num_blocks=40,
                            block_size=4, max_len=80, prefill_chunk=16)
    assert gap > 100 * TIGHT


# ---- the share -----------------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The share test: the routed parts that the chips of the deployment
    compute (here 4 shares of 4 experts of the toy's 16, as the real one's 8
    of 16 of 128) plus the shared expert, counted once, equal what the uncut
    reference layer gives with all 16 experts; and the program's layer, told
    which experts it holds, computes its share.  float32: the sums differ in
    order only, so TIGHT; a dropped assignment moves the sum by a whole
    expert's output."""
    from benchmark.harness import weights

    whole = {**MODEL, "experts_first": 0, "experts_held": 16}
    p = {k: v.astype(jnp.float32)
         for k, v in weights.Maker(whole, 11).layer(1).items()}
    y = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 48), jnp.float32)
    with jax.default_matmul_precision("highest"):
        shared = ref.gated(y, p["shared.w_gate"], p["shared.w_up"],
                           p["shared.w_down"])
        uncut = shared + ref.routed(whole, p, y)
        parts = shared
        for first in (0, 4, 8, 12):
            share = {**whole, "experts_first": first, "experts_held": 4}
            mine = {**p, **{n: p[n][first:first + 4] for n in ref.EXPERTS}}
            part = ref.routed(share, mine, y)
            parts = parts + part
            layer = moe.DroplessMoE(48, 24, 16, top_k=4, held=(first, 4),
                                    shared_ff=24, score="sigmoid",
                                    routed_scale=2.5)
            got, _aux = layer.apply(ref.to_program_layer(share, mine, 1)
                                    ["moe"], y)
            assert close(got, shared + part), first
    assert close(parts, uncut)
    # every token's weights over all 16 sum to 2.5 (top-4, renormalised)
    w = ref.combine_weights(whole, p, y)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.5, rtol=1e-6)
    assert int((np.asarray(w) > 0).sum(-1).max()) == 4


# ---- what cannot run the block says so -----------------------------------

@pytest.mark.parametrize("path", ["dense_cache", "decode_server",
                                  "generate_tp", "speculative", "megatron",
                                  "pipeline", "expert", "scan_layers",
                                  "flash", "kv_quant", "prefix_cache",
                                  "handoff"])
def test_paths_that_cannot_run_the_block_refuse_it_by_name(toy, path):
    import importlib

    net, params = toy[0], toy[1]
    pkg = "neural_networks_parallel_training_with_mpi_tpu."
    kinds = {**net.cfg.__dict__}
    if path == "scan_layers":
        with pytest.raises(ValueError, match="scan_layers"):
            TransformerConfig(**{**kinds, "scan_layers": True})
        return
    if path == "flash":
        with pytest.raises(ValueError, match="no window yet"):
            TransformerConfig(**{**kinds, "attention": "flash"})
        q = jnp.zeros((1, 16, 2, 8))
        with pytest.raises(ValueError, match="sliding window"):
            sequence.sequence_sharded_attention("ring", q, q, q, window=4)
        return
    if path in ("kv_quant", "prefix_cache"):
        with pytest.raises(ValueError, match="window layers"):
            PagedDecodeServer(net, params, slots=2, num_blocks=9,
                              block_size=4, max_len=32, **{path: True})
        return
    if path == "handoff":
        srv = PagedDecodeServer(net, params, slots=2, num_blocks=9,
                                block_size=4, max_len=32)
        rid = srv.try_admit([1, 2, 3], 2)
        srv.prefill_step(rid, 8)
        with pytest.raises(ValueError, match="window layers"):
            srv.export_stream(rid)
        with pytest.raises(ValueError, match="window layers"):
            srv.import_stream({})
        return
    generate, generate_tp, speculative = (
        importlib.import_module(pkg + "models." + n)
        for n in ("generate", "generate_tp", "speculative"))
    serve = importlib.import_module(pkg + "models.serve")
    expert, megatron, pipeline = (
        importlib.import_module(pkg + "parallel." + n)
        for n in ("expert", "megatron", "pipeline"))
    mesh = importlib.import_module(pkg + "parallel.mesh")
    MeshConfig = importlib.import_module(pkg + "config").MeshConfig
    calls = {
        "dense_cache": lambda: generate.init_kv_cache(net, 1, 16),
        "decode_server": lambda: serve.DecodeServer(net, params, slots=1),
        "generate_tp": lambda: generate_tp.init_tp_kv_cache(net, 1, 16, 2),
        "speculative": lambda: speculative._chunk_program(net, 16, 4, False),
        "megatron": lambda: megatron.validate_tp(net.cfg, 2),
        "pipeline": lambda: pipeline._validate_pipe(
            net, mesh.make_mesh(MeshConfig(pipe=2))),
        "expert": lambda: expert.make_moe_train_step(
            net, None, mesh.make_mesh(MeshConfig(expert=2))),
    }
    with pytest.raises(ValueError, match="head width of its own"):
        calls[path]()


# the training step's lowered text (StableHLO, no locations) of two models
# whose layers are all alike, as commit 1e5b130 (PR 32) lowered them: ISSUE 33
# threaded a layer index through ``_block`` / ``backbone`` and asked that the
# programs of such models not move (on the chip their compile-cache keys then
# stay).  A PR that means to change the dense training path re-pins these.
_UNIFORM_TEXT_SHA256 = {
    "dense": "206206cef066f6e83235988028178caab878fb5689d4e0f7c5bb33192c826369",
    "remat": "6a775a033a650f92dd7049af46bb6c94268fcb96a72be5b7e808f999f4cfaf07",
}


@pytest.mark.parametrize("name", sorted(_UNIFORM_TEXT_SHA256))
def test_uniform_models_lower_to_the_parents_text(name):
    import hashlib

    kw = {"dense": dict(n_kv_heads=2, pos_encoding="rope",
                        compute_dtype=jnp.bfloat16),
          "remat": dict(remat=True, activation="swiglu")}[name]
    net = Transformer(TransformerConfig(
        vocab_size=512, max_seq_len=128, n_layers=2, d_model=64, n_heads=4,
        d_ff=256, **kw))
    params = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))
    ids = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    loss = lambda p, x: net.apply(p, x).astype(jnp.float32).mean()  # noqa: E731
    text = jax.jit(jax.value_and_grad(loss)).lower(params, ids).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == _UNIFORM_TEXT_SHA256[name]


def test_the_schedulers_records_carry_both_kinds_of_counter(toy, tmp_path):
    """``kind="serve"`` records and ``tools/metrics_summary.py`` carry the
    attention counters by kind beside the experts', and the ``retire`` span
    of a tick that finished a stream is stamped with both (what the
    benchmark's reducers read)."""
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
        slots=2, num_blocks=40, block_size=4, max_len=80, prefill_chunk=16,
        telemetry_dir=str(tmp_path), metrics_every=1))
    try:
        rids = [sched.submit(list(range(1, n)), 9) for n in (30, 12)]
        sched.run_until_drained()
        assert all(len(sched.result(r)) for r in rids)
        sched.server.assert_drained()
    finally:
        sched.close()
        trace_lib.remove_listener(listener)
        trace_lib.stop_run(tracer)
    final = [r for r in map(json.loads, open(tmp_path / "metrics.jsonl"))
             if r.get("kind") == "serve" and r.get("final")][-1]
    # 8 decode ticks a stream: 1 full layer reads len keys, 4 window layers 8
    assert final["window_keys"] == 4 * 8 * 2 * 8
    assert final["full_keys"] == sum(range(30, 38)) + sum(range(12, 20))
    assert final["full_blocks_held"] > 0 and final["window_blocks_held"] > 0
    assert final["experts_reached"] > 0
    stamped = [a for n, a in spans if n == "retire" and "window_keys" in a]
    assert stamped and stamped[-1]["window_keys"] == final["window_keys"]
    assert "decode_ticks_counted" in stamped[-1]
    out = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "metrics_summary.py"),
         str(tmp_path)], capture_output=True, text=True, check=True).stdout
    assert "keys read by kind of layer" in out


def test_rows_landing_a_program_behind_serve_the_same_tokens(
        toy, staggered_batch):
    """Window and full layers over two kinds of pool under the request
    boundary of ISSUE 36: a finished stream's blocks of both kinds go back
    at its take, its slot is admitted again with the row in flight, and
    every request's tokens are those it gets alone; both pools drain."""
    net, params = toy[0], toy[1]
    rng = np.random.default_rng(3)
    requests = [(rng.integers(0, 96, size=int(rng.integers(2, 40))).tolist(),
                 int(rng.integers(2, 16))) for _ in range(7)]
    sched = staggered_batch(net, params, requests, 2, slots=2, num_blocks=40,
                            block_size=4, max_len=80, prefill_chunk=16)
    assert sched.attention_counters == sched.server.attention_counters
    assert sched.attention_counters["window_keys"] > 0
