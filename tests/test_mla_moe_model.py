"""Latent attention, routing without drops over a held share of experts, the
norm without mean and bias, the gated feed-forward, and the long-context
rotary, each against the plain float32 reference of
``benchmark/families/mla_moe.py`` (which imports nothing from the program), at
a toy size on the CPU.

Everything here is float32 on both sides, so program and reference differ in
the order of float32 sums only.  ``TIGHT`` = 2e-5 of the tensor's scale covers
that (observed at most 3e-6) and nothing else: a router computed in bfloat16
moves a combine weight by about 4e-3, a dropped assignment takes a whole
expert's output (about 0.1 of the scale) away, and both are shown to fail it.
"""

import base64
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.families import mla_moe as ref                    # noqa: E402
from neural_networks_parallel_training_with_mpi_tpu.models import (  # noqa: E402
    Transformer, TransformerConfig, moe,
)
from neural_networks_parallel_training_with_mpi_tpu.models.core import (  # noqa: E402
    RMSNorm,
)
from neural_networks_parallel_training_with_mpi_tpu.ops import rope  # noqa: E402
from neural_networks_parallel_training_with_mpi_tpu.serve import (  # noqa: E402
    PagedDecodeServer, Scheduler, ServeConfig,
)

TIGHT = 2e-5

# the toy: every mechanism of the real configuration, small; the original
# context is 16 positions and the factor 4, so YaRN's blend and the query
# scale bind inside 64 positions
ROPE = {"beta_fast": 4, "beta_slow": 1, "factor": 4,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 16}
MODEL = {"vocab_size": 96, "d_model": 48, "n_layers": 2, "n_heads": 4,
         "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
         "qk_rope_head_dim": 8, "v_head_dim": 12, "expert_ff": 24,
         "shared_experts": 1, "experts_total": 8, "experts_first": 2,
         "experts_held": 4, "top_k": 2, "routed_scale": 1, "max_seq_len": 64,
         "rms_eps": 1e-6, "rope_theta": 10000, "rope": ROPE,
         "param_dtype": "float32", "compute_dtype": "float32",
         "family": ref, "config": "toy"}


def close(a, b, tol=TIGHT):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-30)


def tensors(model=MODEL, seed=5):
    """The benchmark's flat tensors of every layer and of the outer part,
    made as ``harness/weights.py`` makes them."""
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


# ---- the pieces ----------------------------------------------------------

def test_rms_norm_has_no_mean_and_no_bias():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 7, 48)) * 3 + 1.5
    scale = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (48,))
    mine = RMSNorm(48, 1e-6).apply({"scale": scale}, x)
    assert close(mine, ref.rms_norm(x, scale, 1e-6))
    assert list(RMSNorm(48).init(jax.random.PRNGKey(0))) == ["scale"]
    # statistics in float32 whatever the input's type
    low = RMSNorm(48, 1e-6).apply({"scale": scale}, x.astype(jnp.bfloat16))
    assert low.dtype == jnp.bfloat16 and close(
        low.astype(jnp.float32), ref.rms_norm(
            x.astype(jnp.bfloat16).astype(jnp.float32), scale, 1e-6), 1e-2)


def test_gated_feed_forward_without_biases():
    net = Transformer(TransformerConfig(
        vocab_size=32, n_layers=1, d_model=48, n_heads=4, d_ff=40,
        activation="swiglu", use_bias=False, norm="rmsnorm", norm_eps=1e-6))
    blk = net.init(jax.random.PRNGKey(0))["blocks"][0]
    assert all("b" not in blk[n] for n in ("qkv", "attn_out", "ff_in",
                                           "ff_gate", "ff_out"))
    assert list(blk["ln1"]) == ["scale"]
    y = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 48))
    mine = net._ffn(net._block_modules(), blk, y)
    assert close(mine, ref.gated(y, blk["ff_gate"]["w"], blk["ff_in"]["w"],
                                 blk["ff_out"]["w"]))


def test_pair_rotation_yarn_and_query_scale():
    sc = rope.RopeScaling(*ref.rope_scaling_numbers(MODEL))
    # the blend: dimensions that turn 4 times in 16 positions keep their
    # frequency, those that turn once at most have it divided by 4
    mine = rope.yarn_frequencies(8, 10000.0, sc)
    assert close(mine, ref.rope_frequencies(MODEL))
    base = 10000.0 ** (-np.arange(4) / 4)
    assert np.isclose(float(mine[0]), base[0]) and np.isclose(
        float(mine[-1]), base[-1] / 4)
    assert np.isclose(rope.yarn_frequencies(8, 10000.0, None), base).all()
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 3, 8))
    pos = jnp.arange(40)
    assert close(rope.rope_rotate_pairs(x, pos, 10000.0, sc),
                 ref.rotate_pairs(MODEL, x, pos))
    # adjacent pairs: a rotation keeps each pair's norm, and differs from
    # the half-split rotation of rope_rotate
    r = rope.rope_rotate_pairs(x, pos, 10000.0, None)
    assert close((r.reshape(2, 40, 3, 4, 2) ** 2).sum(-1),
                 (x.reshape(2, 40, 3, 4, 2) ** 2).sum(-1))
    assert not close(r, rope.rope_rotate(x, pos, 10000.0), 1e-2)
    # per-row positions (the decode path) agree with one shared row
    rows = jnp.stack([pos, pos + 7])
    both = rope.rope_rotate_pairs(x, rows, 10000.0, sc)
    assert close(both[0], rope.rope_rotate_pairs(x[:1], pos, 10000.0, sc)[0])
    # the query's scale: 1 below the original context, then 1 + 0.1 ln(1+k)
    got = rope.query_scale(jnp.array([0, 15, 16, 31, 32, 63]), sc)
    want = [1, 1, 1 + 0.1 * np.log(2), 1 + 0.1 * np.log(2),
            1 + 0.1 * np.log(3), 1 + 0.1 * np.log(4)]
    assert np.allclose(np.asarray(got), want, rtol=1e-6)
    assert np.isclose(rope.softmax_mscale(sc), (0.1 * np.log(4) + 1) ** 2)
    assert rope.yarn_cos_sin_scale(sc) == 1.0


def test_latent_attention_expanded_against_the_reference_and_absorbed(toy):
    net, params, _outer, layers = toy
    attn = net._block_modules()["attn"]
    ap = params["blocks"][0]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 48, 48))
    with jax.default_matmul_precision("highest"):
        want = ref.attention_half(MODEL, layers[0], x)
    mods = net._block_modules()
    mine = net._latent_attention(mods, params["blocks"][0], x)
    assert close(mine, want)          # positions pass 16 and 32: scale binds
    # the absorbed form is the same mathematics
    h = mods["ln1"].apply(params["blocks"][0]["ln1"], x)
    pos = jnp.broadcast_to(jnp.arange(48)[None], (2, 48))
    q_nope, q_rope, rows = attn.project(ap, h, pos)
    assert rows.shape == (2, 48, 24) and attn.cache_row() == {"latent": (24,)}
    mask = jnp.arange(48)[None, None, :] <= pos[:, :, None]
    expanded = attn.attend_expanded(ap, q_nope, q_rope, rows, mask)
    absorbed = attn.attend_absorbed(ap, q_nope, q_rope, rows, mask)
    assert close(absorbed, expanded)
    # walking the keys block by block up to a bound changes the order of
    # the normaliser's sum and nothing else; queries past the bound (pad
    # columns) read 0
    for n_keys in (48, 33, 16):
        walked = attn.attend_expanded(ap, q_nope, q_rope, rows, mask,
                                      n_keys=jnp.asarray(n_keys),
                                      key_block=16)
        assert close(walked[:, :n_keys], expanded[:, :n_keys])
        assert not np.asarray(jnp.isnan(walked)).any()
    # and it is the scale that makes it so: without it the outputs differ
    plain = Transformer(TransformerConfig(**{
        **net.cfg.__dict__, "rope_scaling": None}))
    assert not close(plain._latent_attention(
        plain._block_modules(), params["blocks"][0], x), want, 1e-3)


# ---- routing without drops -------------------------------------------------

def moe_layer(**kw):
    base = dict(d_model=48, d_ff=24, n_experts=8, top_k=2, held=(2, 4),
                shared_ff=24)
    return moe.DroplessMoE(**{**base, **kw})


def test_routing_without_drops_against_the_reference(toy):
    net, params, _outer, layers = toy
    y = jax.random.normal(jax.random.PRNGKey(3), (2, 40, 48))
    p = layers[1]
    with jax.default_matmul_precision("highest"):
        want = (ref.gated(y, p["shared.w_gate"], p["shared.w_up"],
                          p["shared.w_down"]) + ref.routed(MODEL, p, y))
    layer = net._block_modules()["moe"]
    mine, _aux, load = layer.apply(params["blocks"][1]["moe"], y,
                                   return_load=True)
    assert close(mine, want)
    held = np.asarray(ref.combine_weights(MODEL, p, y) > 0)
    assert load.tolist() == held.reshape(-1, 4).sum(0).tolist()
    # a router in bfloat16 and a dropped assignment both fail the tolerance
    low = {**p, "router.w": p["router.w"].astype(jnp.bfloat16)
           .astype(jnp.float32)}
    assert not close(ref.routed(MODEL, low, y.astype(jnp.bfloat16)
                                .astype(jnp.float32)),
                     ref.routed(MODEL, p, y))
    w = ref.combine_weights(MODEL, p, y)
    first = jnp.argmax(w.reshape(-1, 4).sum(-1) > 0)
    dropped = mine.reshape(-1, 48).at[first].add(
        -ref.routed(MODEL, p, y).reshape(-1, 48)[first])
    assert not close(dropped, want.reshape(-1, 48))


def test_no_token_drops_when_every_token_chooses_one_expert():
    """A router whose scores put expert 3 first for every token: a capacity
    layer would drop all but ``capacity`` of them; here all 64 are computed
    by expert 3 (local 1) and none is lost."""
    layer = moe_layer(top_k=1)
    params = layer.init(jax.random.PRNGKey(0))
    gate = jnp.zeros((48, 8)).at[:, 3].set(1.0)
    params["gate"]["w"] = gate
    y = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (64, 48))) + 0.1
    mine, _aux, load = layer.apply(params, y, return_load=True)
    assert load.tolist() == [0, 64, 0, 0]
    e = params["experts"]
    want = ref.gated(y, e["w_gate"][1], e["w_in"][1], e["w_out"][1]) \
        + layer.shared_ffn(params["shared"], y)
    assert close(mine, want)
    # a masked token reaches no expert: the shared expert's output alone
    mask = jnp.arange(64) % 2 == 0
    half, _aux, load = layer.apply(params, y, mask=mask, return_load=True)
    assert load.tolist() == [0, 32, 0, 0]
    assert close(half[1], layer.shared_ffn(params["shared"], y)[1])
    assert close(half[0], want[0])


def test_tokens_whose_experts_are_all_elsewhere_get_the_shared_expert():
    layer = moe_layer(held=(6, 2))
    params = layer.init(jax.random.PRNGKey(0))
    params["gate"]["w"] = jnp.zeros((48, 8)).at[:, 0].set(2.0).at[:, 1].set(1)
    y = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (10, 48))) + 0.1
    mine, _aux, load = layer.apply(params, y, return_load=True)
    assert load.tolist() == [0, 0]
    assert close(mine, layer.shared_ffn(params["shared"], y))


def test_the_grouped_products_agree():
    """``jax.lax.ragged_dot`` and the Pallas grouped matmul (interpreted on
    the CPU) give the same rows; the rows past the groups are masked."""
    y = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 48))
    a, b = moe_layer(impl="ragged"), moe_layer(impl="gmm")
    params = a.init(jax.random.PRNGKey(0))
    assert close(b.apply(params, y)[0], a.apply(params, y)[0])
    with pytest.raises(ValueError, match="impl must be one of"):
        moe_layer(impl="slices")
    with pytest.raises(ValueError, match="outside the layer's 8"):
        moe_layer(held=(6, 4))


def test_the_capacity_layer_shares_the_router():
    """``MoEFFN`` routes with the same two functions: its top-2 weights are
    ``top_k_weights`` of ``router_probs``."""
    layer = moe.MoEFFN(48, 24, 8, capacity_factor=8.0, router_top_k=2)
    params = layer.init(jax.random.PRNGKey(0))
    y = jax.random.normal(jax.random.PRNGKey(1), (12, 48))
    _dispatch, combine, _aux = layer._route(params["gate"], y, 24)
    w, idx = moe.top_k_weights(moe.router_probs(params["gate"], y), 2)
    got = np.asarray(combine.sum(-1))                       # (N, E)
    want = np.zeros((12, 8), np.float32)
    np.put_along_axis(want, np.asarray(idx), np.asarray(w), axis=1)
    assert np.allclose(got, want, atol=1e-6)


# ---- the whole model --------------------------------------------------------

def test_full_forward_against_the_reference(toy):
    net, params, outer, layers = toy
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, 56), 0, 96)
    assert close(net.apply(params, ids), reference_logits(outer, layers, ids))


def test_gradients_of_the_block_against_the_references(toy):
    """``jax.grad`` through the program's block (sorted dispatch, grouped
    product) against ``jax.grad`` through the reference's (every expert over
    every token), leaf by leaf; 1e-4 of each leaf's scale, an order above
    TIGHT because a gradient sums over 80 tokens."""
    net, params, _outer, layers = toy
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 40, 48))
    probe = jax.random.normal(jax.random.PRNGKey(7), (2, 40, 48))

    def mine(blk):
        return (net._block(blk, x)[0] * probe).sum()

    def theirs(p):
        with jax.default_matmul_precision("highest"):
            return (ref.block(MODEL, p, x, 0) * probe).sum()

    got = ref.layer_leaves(MODEL, jax.grad(mine)(params["blocks"][0]))
    want = jax.grad(theirs)(layers[0])
    assert set(got) == set(want) == set(ref.LAYER)
    for name in want:
        assert close(got[name], want[name], 1e-4), name
    assert float(jnp.abs(want["router.w"]).max()) > 0


def test_fwd_flops_counts_the_held_share():
    net = ref.program_model(MODEL)
    attn = net._block_modules()["attn"]
    per_token = (attn.fwd_flops_per_token(10) + 2 * 48 * 8
                 + 2 * 3 * 48 * 24 * (2 * 4 / 8) + 2 * 3 * 48 * 24)
    assert net.fwd_flops((1, 10)) == pytest.approx(
        10 * (2 * per_token + 2 * 48 * 96))


# ---- through the paged cache ------------------------------------------------

def serve(net, params, prompts, n_new, **cfg):
    base = dict(slots=3, block_size=8, num_blocks=33, max_len=64,
                prefill_chunk=8)
    sched = Scheduler(net, params, ServeConfig(**{**base, **cfg}))
    try:
        rids = [sched.submit(p, n_new) for p in prompts]
        sched.run_until_drained()
        return [sched.result(r) for r in rids], dict(sched.expert_counters)
    finally:
        sched.close()


@pytest.mark.parametrize("impl", ["gathered", "fused"])
def test_prefill_in_chunks_then_decode_through_the_latent_cache(toy, impl):
    """Chunked prefill (expanded) then decode (absorbed), three streams of
    different lengths side by side, against the reference's full forward:
    the prefill program's logits to TIGHT, and every served token the
    reference's own first choice (its logit gap to the reference's best is
    0 in float32).  Under ``fused`` the decode is the paged kernel's
    shared-row walk over the pool stored 128 lanes wide (interpreted here),
    and the chunk gathers its stream's rows from that pool: the same
    tokens, so ``fused`` equals ``gathered`` token for token."""
    net, params, outer, layers = toy
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 96, size=n).tolist() for n in (5, 19, 30)]
    served, counters = serve(net, params, prompts, 20, attn_impl=impl)
    for prompt, toks in zip(prompts, served):
        assert toks[:len(prompt)] == prompt and len(toks) == len(prompt) + 20
        logits = reference_logits(outer, layers, jnp.asarray([toks]))[0]
        at = logits[len(prompt) - 1:-1]
        gap = at.max(-1) - at[jnp.arange(20), jnp.asarray(toks[len(prompt):])]
        assert float((gap / at.std(-1)).max()) <= 1e-5
    assert counters["decode_ticks_counted"] > 0
    assert counters["prefill_chunks_counted"] == 1 + 3 + 4
    assert 0 < counters["experts_reached"] <= (
        counters["decode_ticks_counted"] * 2 * 4)
    assert counters["expert_assignments"] >= counters[
        "prefill_expert_assignments"] > 0
    # the prefill program's own logits, one chunk of a 30-token prompt: the
    # program returns the row of its last true column, so each position is
    # read as the last of its prefix (the same chunk, ``true_w`` shorter)
    srv = PagedDecodeServer(net, params, slots=2, num_blocks=17,
                            block_size=8, max_len=64, attn_impl=impl)
    rid = srv.try_admit(prompts[2], 4)
    slot = srv._slot_of[rid]
    rows = []
    for true_w in range(1, 31):
        row, srv.pools, srv.stats = srv._prefill_fn(
            srv.params, srv.pools, srv.stats,
            jnp.asarray(srv.tables[slot:slot + 1].copy()),
            jnp.asarray([0], jnp.int32),
            jnp.asarray([prompts[2] + [0, 0]], jnp.int32),
            jnp.asarray(true_w, jnp.int32), jnp.asarray(True))
        rows.append(row)
    want = reference_logits(outer, layers, jnp.asarray([prompts[2]]))
    assert close(jnp.stack(rows, axis=1), want)


def dense_toy():
    net = Transformer(TransformerConfig(
        vocab_size=96, max_seq_len=64, n_layers=2, d_model=48, n_heads=4,
        n_kv_heads=2, d_ff=64, pos_encoding="rope"))
    return net, net.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module", params=["per_head", "latent",
                                        "latent-fused"])
def row_kind(request, toy):
    """The same cache tests over both answers to ``cache_row()``, the
    latent row under both implementations (``fused`` stores it padded to
    128 lanes; the per-head row under the kernel is tests/test_paged_attn
    .py's): ``(kind, net, params, attn_impl)``."""
    kind, _, impl = request.param.partition("-")
    if kind == "latent":
        return kind, toy[0], toy[1], impl or "gathered"
    return (kind, *dense_toy(), "gathered")


def drain(srv, rid, width=4):
    while not srv.prefill_step(rid, width):
        pass
    while not srv.done(rid):
        srv.step()
    return srv.result(rid)


def test_pools_follow_the_attentions_row(row_kind):
    kind, net, params, impl = row_kind
    srv = PagedDecodeServer(net, params, slots=2, num_blocks=9, block_size=8,
                            max_len=64, attn_impl=impl)
    shapes = {n: p.shape for n, p in srv.pools[0].items()}
    if kind == "latent":
        # stored in whole lane tiles under the kernel; what leaves the
        # server is the attention's own row either way
        assert shapes == {"latent": (9, 8, 128 if impl == "fused" else 24)}
        assert srv._handoff_geometry()["row"] == {"latent": [24]}
    else:
        assert shapes == {"k": (9, 8, 2, 12), "v": (9, 8, 2, 12)}
        assert srv._handoff_geometry()["row"] == {"k": [2, 12], "v": [2, 12]}


def test_copy_on_write_with_either_row(row_kind):
    """A warm admission shares the prompt's blocks and forks the partial
    tail block before writing into it; tokens equal the cold run's and the
    run's without the cache."""
    _kind, net, params, impl = row_kind
    prompt, n = list(range(1, 21)), 8          # bs 8: 2 full blocks + 4
    on = PagedDecodeServer(net, params, slots=4, num_blocks=40, block_size=8,
                           max_len=64, prefix_cache=True, attn_impl=impl)
    cold = drain(on, on.try_admit(prompt, n))
    warm_rid = on.try_admit(prompt, n)
    assert on.prefill_remaining(warm_rid) == 1
    warm = drain(on, warm_rid)
    assert on.cow_forks == 1
    off = PagedDecodeServer(net, params, slots=4, num_blocks=40, block_size=8,
                            max_len=64, attn_impl=impl)
    assert cold == warm == drain(off, off.try_admit(prompt, n))
    on.allocator.assert_drained()


def test_export_and_import_with_either_row(row_kind):
    """A prefilled stream's block rows travel as bytes and decode on the
    importing server to the tokens of an undivided run."""
    kind, net, params, impl = row_kind
    prompt, n = list(range(3, 24)), 9
    make = lambda: PagedDecodeServer(                          # noqa: E731
        net, params, slots=2, num_blocks=17, block_size=8, max_len=64,
        attn_impl=impl)
    a, b, c = make(), make(), make()
    whole = drain(c, c.try_admit(prompt, n))
    rid = a.try_admit(prompt, n)
    while not a.prefill_step(rid, 8):
        pass
    payload = a.export_stream(rid)
    assert payload["n_blocks"] == 3
    assert set(payload["layers"][0]) == set(net.cache_row())
    # the attention's own row travels, whatever the pools store
    wide = sum(int(np.prod(r)) for r in net.cache_row().values())
    assert sum(len(base64.b64decode(v)) for v in payload["layers"][0]
               .values()) == 3 * 8 * wide * 4
    rid_b = b.import_stream(payload)
    while not b.done(rid_b):
        b.step()
    assert b.result(rid_b) == whole
    other = dense_toy()[0] if kind == "latent" else ref.program_model(MODEL)
    foreign = PagedDecodeServer(other, other.init(jax.random.PRNGKey(0)),
                                slots=2, num_blocks=17, block_size=8,
                                max_len=64)
    with pytest.raises(ValueError, match="handoff geometry mismatch"):
        foreign.import_stream(payload)


def test_prefill_to_decode_handoff_with_either_row(row_kind):
    """A prefill-role scheduler exports at the prefill/decode boundary and a
    decode-role scheduler takes the stream on: the unified run's tokens."""
    _kind, net, params, impl = row_kind
    prompt, n = list(range(5, 30)), 7
    cfg = dict(slots=2, block_size=8, num_blocks=17, max_len=64,
               prefill_chunk=8, attn_impl=impl)
    want = serve(net, params, [prompt], n, **cfg)[0][0]
    pre = Scheduler(net, params, ServeConfig(role="prefill", **cfg))
    dec = Scheduler(net, params, ServeConfig(role="decode", **cfg))
    try:
        pre.submit(prompt, n)
        handoffs = []
        for _ in range(20):
            pre.tick()
            handoffs += pre.take_handoffs()
            if handoffs:
                break
        assert len(handoffs) == 1
        rid = dec.inject(handoffs[0]["payload"])
        dec.run_until_drained()
        assert dec.result(rid) == want
    finally:
        pre.close()
        dec.close()


# ---- the kernel over the latent row; what cannot run it yet says so ---------

def test_latent_rows_run_the_fused_kernel_and_refuse_int8(toy):
    """``fused`` serves the latent row (it refused it by name until PR 32):
    staggered admissions, a prompt prefilled in chunks that straddle block
    borders, an eviction in mid-stream and its re-admission give the
    ``gathered`` server's tokens; the pool drains.  int8 pools still have
    no scheme for the row, under either implementation."""
    net, params = toy[0], toy[1]

    def scenario(impl):
        srv = PagedDecodeServer(net, params, slots=3, num_blocks=33,
                                block_size=8, max_len=64, attn_impl=impl)
        a = srv.try_admit(list(range(1, 12)), 12)
        while not srv.prefill_step(a, 4):
            pass
        srv.step(); srv.step()
        b = srv.try_admit([7, 8], 9)
        while not srv.prefill_step(b, 16):
            pass
        srv.step(); srv.step(); srv.step()
        prompt, max_new = srv.evict(b)
        c = srv.try_admit(prompt, max_new)
        out = [drain(srv, c, 16)]
        while not srv.done(a):
            srv.step()
        out.append(srv.result(a))
        srv.allocator.assert_drained()
        return out

    assert scenario("fused") == scenario("gathered")
    for impl in ("auto", "fused"):
        with pytest.raises(ValueError, match="int8 codes of per-head K and "
                                             "V"):
            PagedDecodeServer(net, params, slots=2, num_blocks=9,
                              block_size=8, max_len=64, kv_quant=True,
                              attn_impl=impl)


def test_garbage_past_a_streams_length_changes_nothing_under_fused(toy):
    """Non-zero garbage planted in every pool position no stream holds (the
    sink, free blocks, the tail of each stream's last page) before and
    between decode steps: the tokens of the untouched run."""
    net, params = toy[0], toy[1]

    def run(dirty):
        srv = PagedDecodeServer(net, params, slots=2, num_blocks=17,
                                block_size=8, max_len=64, attn_impl="fused")
        rid = srv.try_admit(list(range(2, 13)), 10)      # 11: 1 block + 3
        while not srv.prefill_step(rid, 8):
            pass
        while not srv.done(rid):
            if dirty:
                held = np.zeros((17, 8), bool)
                n = int(srv._pos_host[srv._slot_of[rid]])   # rows written
                blocks = srv._streams[rid].blocks
                for t in range(n):
                    held[blocks[t // 8], t % 8] = True
                keep = jnp.asarray(held)[..., None]
                srv.pools = [{"latent": jnp.where(keep, p["latent"], 1e4)}
                             for p in srv.pools]
            srv.step()
        return srv.result(rid)

    assert run(True) == run(False)


def test_latent_table_churn_and_growth_never_recompile_under_fused(toy):
    """Tables and lengths are traced operands of the latent decode program
    too: admission, growth over a block border, eviction and re-admission
    re-run one compiled step; the compile ledger's event of that step names
    the kernel with its tiling, the prefill buckets say what they run, and
    the scheduler's records read ``walked_keys_share`` below 1."""
    import json
    import os
    import tempfile

    from neural_networks_parallel_training_with_mpi_tpu.utils import (
        compile_ledger,
    )

    net, params = toy[0], toy[1]
    led = compile_ledger.Ledger(None)
    compile_ledger.install(led)
    try:
        # a geometry no other test of this file uses: the programs are new
        srv = PagedDecodeServer(net, params, slots=3, num_blocks=25,
                                block_size=8, max_len=56, attn_impl="fused")
        a = srv.try_admit([1] * 12, 12)
        while not srv.prefill_step(a, 16):
            pass
        for _ in range(4):
            srv.step()
        n_events = len(led.events)
        b = srv.try_admit([9] * 11, 8)
        while not srv.prefill_step(b, 16):
            pass
        srv.step()
        srv.evict(b)
        c = srv.try_admit([3] * 9, 6)
        while not srv.prefill_step(c, 16):
            pass
        while not (srv.done(a) and srv.done(c)):
            srv.step()
        srv.allocator.assert_drained()
        # (the first stream to finish compiles the take of its row: one
        # program, whichever slot; nothing of the churn compiled)
        assert [e["name"] for e in led.events[n_events:]] \
            == ["serve_take[bs8x7]"]
    finally:
        compile_ledger.install(None)
    decode = led.events_for("serve_decode[bs8x7/fused]")
    assert len(decode) == 1 and decode[0]["attention"] == {
        "impl": "paged", "pages": 7, "tile_cols": 1, "block_size": 8}
    prefill = led.events_for("serve_prefill[bs8x7/fused]")
    assert prefill and all(e["attention"] == {"impl": "gathered", "keys": 56}
                           for e in prefill)
    # the grouped expert products say what implements them (the CPU's)
    assert all(e["experts"] == {"impl": "ragged"} for e in decode + prefill)
    with tempfile.TemporaryDirectory() as tdir:
        served, _ = serve(net, params, [list(range(1, 20))], 12, max_len=64,
                          attn_impl="fused", telemetry_dir=tdir,
                          metrics_every=1)
        finals = [r for r in map(json.loads, open(os.path.join(
            tdir, "metrics.jsonl"))) if r.get("kind") == "serve"
            and r.get("final")]
    assert len(served[0]) == 19 + 12
    assert 0 < finals[-1]["walked_keys_share"] < 1


# ---- what ``attn_impl="auto"`` resolves to ----------------------------------

def _lane_dense_toy():
    """Per-head K and V whose row fills the lanes: 2 KV heads of 64."""
    return Transformer(TransformerConfig(
        vocab_size=96, max_seq_len=64, n_layers=1, d_model=256, n_heads=4,
        n_kv_heads=2, d_ff=64, pos_encoding="rope"))


@pytest.mark.parametrize("backend,model,kv_quant,want", [
    ("cpu", "lane_dense", False, "gathered"),
    ("tpu", "lane_dense", False, "fused"),
    ("tpu", "lane_dense", True, "gathered"),     # the int8 walk was not timed
    ("tpu", "narrow", False, "gathered"),        # 2 x 12 lanes: not a page DMA
    ("tpu", "latent", False, "fused"),           # stored in whole lane tiles
    ("tpu", "latent", True, "gathered"),         # (and then refused: no int8)
    ("cpu", "latent", False, "gathered"),
], ids=["cpu", "tpu-per-head", "tpu-int8", "tpu-narrow-row", "tpu-latent",
        "tpu-latent-int8", "cpu-latent"])
def test_auto_resolves_from_backend_row_and_shapes(toy, monkeypatch, backend,
                                                   model, kv_quant, want):
    """``auto`` looks at the backend, the cache row and the row's width,
    and at nothing else; explicit values are kept as given."""
    from neural_networks_parallel_training_with_mpi_tpu.serve import paged_kv

    net = {"lane_dense": _lane_dense_toy, "narrow": lambda: dense_toy()[0],
           "latent": lambda: toy[0]}[model]()
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert paged_kv.resolve_attn_impl(net, "auto", kv_quant) == want
    assert paged_kv.resolve_attn_impl(net, "gathered", kv_quant) == "gathered"
    assert paged_kv.resolve_attn_impl(net, "fused", kv_quant) == "fused"
    with pytest.raises(ValueError, match="attn_impl must be one of"):
        paged_kv.resolve_attn_impl(net, "flash", kv_quant)


def test_auto_is_the_default_and_the_server_says_what_runs(toy):
    """On the CPU the default is the gathered path over 4-D pools, for
    either row; the fused path stores its pools folded, the same bytes."""
    assert ServeConfig().attn_impl == "auto"
    net, params = dense_toy()
    srv = PagedDecodeServer(net, params, slots=2, num_blocks=9, block_size=8,
                            max_len=64)
    assert srv.attn_impl == "gathered"
    assert srv.pools[0]["k"].shape == (9, 8, 2, 12)
    fused = PagedDecodeServer(net, params, slots=2, num_blocks=9,
                              block_size=8, max_len=64, attn_impl="fused")
    assert fused.attn_impl == "fused"
    assert fused.pools[0]["k"].shape == (9, 8, 24)
    assert fused._handoff_geometry() == srv._handoff_geometry()
    assert PagedDecodeServer(toy[0], toy[1], slots=2, num_blocks=9,
                             block_size=8, max_len=64).attn_impl == "gathered"


@pytest.mark.parametrize("row", ["per_head", "latent"])
def test_fused_exports_to_a_gathered_importer_and_back(toy, row):
    """A block row's bytes are the same folded or not, and the latent row
    travels 24 lanes wide though the kernel's pool stores 128: a stream
    prefilled under the kernel decodes on a gathered server to the tokens
    of an undivided run, and the other way round."""
    net, params = dense_toy() if row == "per_head" else toy[:2]
    prompt, n = list(range(3, 24)), 9
    make = lambda impl: PagedDecodeServer(                      # noqa: E731
        net, params, slots=2, num_blocks=17, block_size=8, max_len=64,
        attn_impl=impl)
    ref_srv = make("gathered")
    whole = drain(ref_srv, ref_srv.try_admit(prompt, n))
    for src, dst in (("fused", "gathered"), ("gathered", "fused")):
        a, b = make(src), make(dst)
        rid = a.try_admit(prompt, n)
        while not a.prefill_step(rid, 8):
            pass
        payload = a.export_stream(rid)
        assert payload["geom"] == b._handoff_geometry()
        rid_b = b.import_stream(payload)
        while not b.done(rid_b):
            b.step()
        assert b.result(rid_b) == whole, (src, dst)


# the latent programs' lowered text (StableHLO, no locations) of the toy
# below.  ``decode`` as commit 678b5f3 (PR 29) lowered it: ISSUE 30 changed
# the per-head path beside it and asked that this one not move.  ``prefill``
# re-pinned by PR 38 (the chunk's head on its last true column alone, under
# ``last``: ISSUE 38 asked that ``decode`` stay byte for byte).  A PR that
# means to change the latent path re-pins what it moves.
_LATENT_TEXT_SHA256 = {
    "decode": "26a4c660ac11aa7dd14e6f7b4ef8cfc6ea785bd766cc8324d7b2cdd1dd0f5b35",
    "prefill": "ac7e7ef331dc5467e605e463da3618ae1e1647f2d51abc150f1b1555b4e2c708",
}


def test_latent_programs_lower_to_the_parents_text(toy):
    import hashlib

    srv = PagedDecodeServer(toy[0], toy[1], slots=2, num_blocks=9,
                            block_size=8, max_len=64)
    text = {
        "decode": srv._step_fn.lower(
            srv.params, srv.pools, srv.stats, srv.tokens,
            jnp.asarray(srv.tables), srv.pos, jnp.asarray(srv.active),
            srv.key).as_text(),
        "prefill": srv._prefill_fn.lower(
            srv.params, srv.pools, srv.stats, jnp.asarray(srv.tables[:1]),
            jnp.zeros((1,), jnp.int32), jnp.zeros((1, 8), jnp.int32),
            jnp.asarray(5, jnp.int32), jnp.asarray(True)).as_text()}
    got = {k: hashlib.sha256(v.encode()).hexdigest() for k, v in text.items()}
    assert got == _LATENT_TEXT_SHA256


@pytest.mark.parametrize("path", ["dense_cache", "decode_server",
                                  "generate_tp", "speculative", "megatron",
                                  "pipeline", "expert"])
def test_paths_that_cannot_run_the_block_refuse_it_by_name(toy, path):
    import importlib

    pkg = "neural_networks_parallel_training_with_mpi_tpu.models."
    generate, generate_tp, speculative = (
        importlib.import_module(pkg + n)
        for n in ("generate", "generate_tp", "speculative"))
    from neural_networks_parallel_training_with_mpi_tpu.models.serve import (
        DecodeServer,
    )
    from neural_networks_parallel_training_with_mpi_tpu.parallel import (
        expert, megatron, pipeline,
    )
    from neural_networks_parallel_training_with_mpi_tpu.parallel.mesh import (
        make_mesh,
    )
    from neural_networks_parallel_training_with_mpi_tpu.config import (
        MeshConfig,
    )

    net, params = toy[0], toy[1]
    with pytest.raises(ValueError, match="latent attention .* and routing "
                                         "without drops"):
        if path == "dense_cache":
            generate.init_kv_cache(net, 1, 16)
        elif path == "decode_server":
            DecodeServer(net, params, slots=1)
        elif path == "generate_tp":
            generate_tp.init_tp_kv_cache(net, 1, 16, 2)
        elif path == "speculative":
            speculative._chunk_program(net, 16, 4, False)
        elif path == "megatron":
            megatron.validate_tp(net.cfg, 2)
        elif path == "pipeline":
            pipeline._validate_pipe(net, make_mesh(MeshConfig(pipe=2)))
        else:
            expert.make_moe_train_step(net, None, make_mesh(
                MeshConfig(expert=2)))


def test_config_refuses_what_the_block_cannot_be():
    base = dict(vocab_size=32, n_layers=1, d_model=48, n_heads=4, d_ff=24,
                attention_kind="mla", q_lora_rank=8, kv_lora_rank=8,
                qk_nope_head_dim=4, qk_rope_head_dim=4, v_head_dim=4)
    with pytest.raises(ValueError, match="pos_encoding must be 'rope'"):
        TransformerConfig(**base)
    with pytest.raises(ValueError, match="attention='ring'"):
        TransformerConfig(**base, pos_encoding="rope", attention="ring")
    with pytest.raises(ValueError, match="five sizes"):
        TransformerConfig(**{**base, "v_head_dim": 0}, pos_encoding="rope")
    with pytest.raises(ValueError, match="norm must be"):
        TransformerConfig(norm="batchnorm")
    with pytest.raises(ValueError, match="belongs to the capacity layer"):
        TransformerConfig(moe_experts=4, moe_dropless=True,
                          moe_expert_axis="expert")


@pytest.mark.parametrize("impl", ["gathered", "fused"])
def test_rows_landing_a_program_behind_serve_the_same_tokens(
        toy, staggered_batch, impl):
    """The latent row and the routing without drops under the request
    boundary of ISSUE 36: eight requests over three callers, rows landing
    behind the next tick's programs, slots admitted again meanwhile: every
    request's tokens are those it gets alone, and the expert counters that
    ride on the landed rows still count every program."""
    net, params = toy[0], toy[1]
    rng = np.random.default_rng(2)
    requests = [(rng.integers(0, 96, size=int(rng.integers(2, 30))).tolist(),
                 int(rng.integers(2, 14))) for _ in range(8)]
    sched = staggered_batch(net, params, requests, 3, slots=3, block_size=8,
                            num_blocks=33, max_len=64, prefill_chunk=8,
                            attn_impl=impl)
    counters = sched.expert_counters
    chunks = sum(-(-len(p) // 8) for p, _ in requests)
    assert counters["prefill_chunks_counted"] == chunks
    # every decode tick but those after the last landed row's take (none:
    # the drain's last row lands behind nothing, with the last tick's count)
    assert counters["decode_ticks_counted"] > 0
    assert counters == sched.server.expert_counters
