"""Gated DeltaNet layers in place of attention in three layers of every four,
post-norm blocks, full layers without positions whose q and k are normed over
the whole projection (``models/deltanet.py``, ``models/transformer.py``,
``serve/paged_kv.py``), against the plain float32 reference of
``benchmark/families/olmo_hybrid.py`` (which imports nothing from the program
and runs the delta rule one step a token), at a toy of 8 layers ``D D D G``
twice on the CPU.

Everything here is float32 on both sides, so program and reference differ in
the order of float32 sums only.  ``TIGHT`` = 2e-5 of the tensor's scale covers
that and nothing else: a reference without one piece (the post-norm, the norm
of q and k over the whole projection, beta's factor 2, the decay) is shown to
break it by a hundredfold.
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
from neural_networks_parallel_training_with_mpi_tpu.models import (  # noqa: E402
    Transformer, TransformerConfig,
)
from neural_networks_parallel_training_with_mpi_tpu.models.generate import (  # noqa: E402
    init_kv_cache,
)
from neural_networks_parallel_training_with_mpi_tpu.serve import (  # noqa: E402
    PagedDecodeServer, Scheduler, ServeConfig,
)

TIGHT = 2e-5

# the toy: every mechanism of the real configuration, small; grouped K/V
# heads, so that a k norm as wide as q's would show; values of 64, stored two
# rows of the state to 128 lanes as the real 192 are; prefill chunks of 8, so
# that the streams below carry the state across several (the rule's tiles
# inside one form are tests/test_deltanet.py's)
KINDS = [ref.LINEAR] * 3 + [ref.FULL]
MODEL = {"vocab_size": 96, "d_model": 48, "n_layers": 8, "n_heads": 4,
         "n_kv_heads": 2, "layer_types": KINDS * 2, "d_ff": 80,
         "gdn_key_heads": 3, "gdn_value_heads": 3, "gdn_key_dim": 8,
         "gdn_value_dim": 64, "gdn_conv": 4, "gdn_neg_eigval": True,
         "max_seq_len": 128, "rms_eps": 1e-6,
         "param_dtype": "float32", "compute_dtype": "float32",
         "family": ref, "config": "toy"}
SERVE = dict(slots=2, num_blocks=48, block_size=4, max_len=80,
             prefill_chunk=8)


def close(a, b, tol=TIGHT):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-30)


@pytest.fixture(scope="module")
def toy():
    from benchmark.harness import weights

    maker = weights.Maker(MODEL, 5)
    outer, layers = maker.outer(), maker.layers()
    net = ref.program_model(MODEL)
    return net, ref.to_program(MODEL, outer, layers), outer, layers


def reference_logits(outer, layers, ids, model=MODEL):
    with jax.default_matmul_precision("highest"):
        x = ref.embed(model, outer, ids)
        for i, p in enumerate(layers):
            x = ref.block(model, p, x, i)
        return ref.head_logits(model, outer, x)


IDS = np.random.default_rng(0).integers(0, 96, size=(2, 37))


# ---- the model -------------------------------------------------------------

def test_the_layers_are_of_the_configs_kinds(toy):
    net = toy[0]
    c = net.cfg
    assert c.attention_pattern == "DDDGDDDG"
    assert [c.layer_has_cache(i) for i in range(8)] == [False] * 3 + [
        True] + [False] * 3 + [True]
    assert [c.layer_is_recurrent(i) for i in range(8)] == [
        not c.layer_has_cache(i) for i in range(8)]
    assert [c.layer_rotary(i) for i in range(8)] == [None] * 8
    assert set(net._block_modules(0)) == {"ln1", "gdn", "ln2", "ff_in",
                                          "ff_gate", "ff_out"}
    assert net._block_modules(3)["k_norm"].dim == 2 * 12
    assert net.state_row(3) == {} and net.state_row() == net.state_row(0)


def test_apply_matches_the_reference(toy):
    net, params, outer, layers = toy
    want = reference_logits(outer, layers, IDS)
    mine = net.apply(params, jnp.asarray(IDS))
    assert mine.shape == (2, 37, 96) and close(mine, want)
    # the training forward has a gradient (no cell trains it)
    loss = lambda p: net.apply(p, jnp.asarray(IDS[:1, :13])).var()  # noqa: E731
    grads = jax.grad(loss)(params)
    norms = jax.tree_util.tree_map(lambda g: float(jnp.abs(g).max()),
                                   grads["blocks"][0]["gdn"])
    assert all(np.isfinite(v) and v > 0
               for v in jax.tree_util.tree_leaves(norms)), norms


def _pre_norm_block(model, p, x, i, quant=None):
    eps = model["rms_eps"]
    y = ref.rms_norm(x, p["norm1.scale"], eps)
    x = x + (ref.gdn(model, p, y) if "gdn_in.w" in p
             else ref.attention(model, p, y))
    return x + ref.mlp(model, p, ref.rms_norm(x, p["norm2.scale"], eps))


def _per_head_attention(model, p, x, quant=None):
    """The full layer with q and k normed a head (each head's lanes of the
    whole scale), the alternative ``assumed`` did not take."""
    h, kv, hd = model["n_heads"], model["n_kv_heads"], ref.head_dim(model)
    b, t, _ = x.shape
    qkv = x @ p["qkv.w"]
    q = ref.rms_norm(qkv[..., :h * hd].reshape(b, t, h, hd),
                     p["q_norm.scale"].reshape(h, hd), model["rms_eps"])
    k = ref.rms_norm(qkv[..., h * hd:(h + kv) * hd].reshape(b, t, kv, hd),
                     p["k_norm.scale"].reshape(kv, hd), model["rms_eps"])
    v = qkv[..., (h + kv) * hd:].reshape(b, t, kv, hd)
    q = q.reshape(b, t, kv, h // kv, hd)
    s = jnp.einsum("bqcgd,bkcd->bcgqk", q, k) * hd ** -0.5
    seen = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(seen, s, -jnp.inf)
    o = jnp.einsum("bcgqk,bkcd->bqcgd", jax.nn.softmax(s, -1), v)
    return o.reshape(b, t, h * hd) @ p["attn_out.w"]


FAULTS = {
    "pre_norm": lambda mp: mp.setattr(ref, "block", _pre_norm_block),
    "qk_norm_per_head": lambda mp: mp.setattr(ref, "attention",
                                              _per_head_attention),
    "beta_without_2": None,
    "no_decay": lambda mp: mp.setattr(
        ref, "decay_of", lambda model, p, a: jnp.ones_like(a)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_reference_without_one_piece_is_another_model(toy, monkeypatch,
                                                        fault):
    net, params, outer, layers = toy
    model = MODEL
    if FAULTS[fault] is None:
        model = {**MODEL, "gdn_neg_eigval": False}
    else:
        FAULTS[fault](monkeypatch)
    other = reference_logits(outer, layers, IDS[:1], model)
    mine = net.apply(params, jnp.asarray(IDS[:1]))
    assert not close(mine, other, 100 * TIGHT)


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
    counters = dict(sched.ssm_counters)
    sched.close()
    return worst, counters


PROMPTS = [np.random.default_rng(1).integers(0, 96, size=n).tolist()
           for n in (50, 9, 23)]


@pytest.mark.parametrize("impl", ["gathered", "fused"])
def test_chunked_prefill_then_decode_match_the_full_forward(toy, impl):
    """Prefill in chunks of 8 from a carried state and decode beside other
    streams, streams of up to 70 positions, against the reference's one full
    forward pass: every served token is the reference's greedy choice to
    within float32 noise.  The counters count the six delta-rule layers."""
    net, params, outer, layers = toy
    gap, counters = served_logits_gap(net, params, outer, layers, PROMPTS,
                                      (20, 12, 7), attn_impl=impl, **SERVE)
    assert gap <= TIGHT
    assert counters["ssm_prefill_tokens"] == 6 * sum(map(len, PROMPTS))
    assert counters["ssm_state_updates"] % 6 == 0


def test_the_served_path_fails_a_planted_fault(toy):
    """The program with beta in (0, 1): the served tokens leave the
    reference's greedy choices."""
    net, params, outer, layers = toy
    other = Transformer(TransformerConfig(**{**net.cfg.__dict__,
                                             "gdn_neg_eigval": False}))
    gap, _ = served_logits_gap(other, params, outer, layers, PROMPTS,
                               (20, 12, 7), **SERVE)
    assert gap > 100 * TIGHT


def test_the_server_holds_pools_and_state_rows_by_kind(toy):
    """A pool for each of the two full layers alone, a state row store for
    each of the six delta-rule layers alone, in layer order."""
    net, params = toy[0], toy[1]
    srv = PagedDecodeServer(net, params, **SERVE)
    assert len(srv.pools) == 2
    assert [(p["k"].shape, p["v"].shape) for p in srv.pools] == [
        ((48, 4, 2, 12), (48, 4, 2, 12))] * 2
    assert [(s["conv"].shape, s["state"].shape, s["state"].dtype)
            for s in srv.state] == [((2, 3, 240), (2, 3, 4, 128),
                                     jnp.float32)] * 6
    assert set(srv.stats) == {"ssm"}


def test_paths_that_cannot_run_the_block_refuse_it_by_name(toy):
    net, params = toy[0], toy[1]
    refused = "recurrent state.*delta-rule layers"
    with pytest.raises(ValueError, match=refused):
        net.cfg.require_plain_block("the dense KV cache")
    with pytest.raises(ValueError, match=refused):
        init_kv_cache(net, 1, 16)
    with pytest.raises(ValueError, match="post_norm"):
        TransformerConfig(post_norm=True).require_plain_block("a path")
    with pytest.raises(ValueError, match="gdn_heads"):
        TransformerConfig(attention_pattern="DG")
    with pytest.raises(ValueError, match="qk_norm_whole"):
        TransformerConfig(qk_norm_whole=True)
    kw = dict(slots=2, num_blocks=16, block_size=4, max_len=32)
    for flag in (dict(prefix_cache=True), dict(kv_quant=True)):
        with pytest.raises(ValueError, match=refused):
            PagedDecodeServer(net, params, **kw, **flag)
    srv = PagedDecodeServer(net, params, **kw)
    rid = srv.try_admit([1, 2, 3], 2)
    srv.prefill_step(rid, 8)
    with pytest.raises(ValueError, match="export_stream.*" + refused):
        srv.export_stream(rid)
    with pytest.raises(ValueError, match="import_stream.*" + refused):
        srv.import_stream({})


def test_the_counts(toy):
    """The model counts each kind of layer's products in ``fwd_flops``."""
    net = toy[0]
    gdn = net._block_modules(0)["gdn"]
    c = net.cfg
    b, t, d, ff, v = 2, 10, 48, 80, 96
    full = (2 * b * t * d * c.qkv_dim + 2 * b * t * c.q_dim * d
            + 2 * 2 * b * t * t * c.q_dim)
    linear = b * t * (2 * d * (gdn.in_dim + gdn.v_width)
                      + ref.recurrence_flops(MODEL))
    want = 2 * full + 6 * linear + 8 * 2 * 3 * b * t * d * ff \
        + 2 * b * t * d * v
    assert net.fwd_flops((b, t)) == pytest.approx(want)


# ---- the programs' lowered text --------------------------------------------

# the toy's training step and paged programs as commit 61f07d9 lowered them
# (StableHLO, no locations; the delta rule's tick kernel and the paged
# attention kernel interpreted under ``fused``, so their bodies are in it),
# before the paged programs ran the model's own block with the cache's cores
# passed in: that move was to change no operation and no order of operations
# (on the chip the programs' compile-cache keys then stay).  A change that
# means to move them re-pins these.
_TEXT_SHA256 = {
    "train": "1b1d2a0c989615c9ad0631ea39bf4203164c27e79f6434fb9ca19c47401fbd47",
    "decode": "653501948581e7e6d83f9c93df494d301f838422ac7c4fe768c87e6652431b4f",
    "decode_fused":
        "8a0f8364bbd0791598c2fc6df2b8a524a68c83a6616c08db56aa2fb053f1d22b",
    "prefill": "61fc6828c2f0c2275d036a8616a66a7b0f2f79e4c37afd0b15fad8f0ab30f4b8",
}


@pytest.fixture(scope="module")
def lowered(toy, lowered_programs):
    fused = lowered_programs(toy[0], **SERVE, attn_impl="fused")
    return {**lowered_programs(toy[0], **SERVE),
            "decode_fused": fused["decode"]}


@pytest.mark.parametrize("program", sorted(_TEXT_SHA256))
def test_the_programs_lower_to_the_parents_text(lowered, program):
    import hashlib

    text = lowered[program].as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == _TEXT_SHA256[program]
