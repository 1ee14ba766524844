"""Family ``mla_moe`` (latent attention, routing without drops over a held
share of experts beside a shared expert) at a toy size on the CPU: through the
unmodified ``harness/serve_closed_loop.py`` and ``harness/train.py``, the
control and a planted fault, the share test, and the counts at the published
widths by hand.

The toy (``tests/data/configs/tiny-mla-moe.json``) is float32 throughout, so
its limits are tight; it has ``original_max_position_embeddings`` 16 and factor
4, so that YaRN's blend and the query scale by position bind inside the toys'
64 to 128 positions.
"""

import json

import numpy as np
import pytest

from conftest import BENCH


def real_model():
    from benchmark.harness import common

    return common.load_cell("ms4-119b-ep4-serve-docqa")["model"]


def failed(res):
    return sorted(c["name"] for c in res["checks"] if not c["ok"])


def test_serve_cell_is_correct_and_reports_the_expert_rows(run_cell,
                                                           bench_dir):
    """Chunked prefill (expanded form) then decode (absorbed form) through
    the latent cache against the reference's full forward pass; the counters
    reach ``obs["spans"]`` on the ``retire`` spans and the two counter rows
    read them."""
    from benchmark import run as runner

    cell, dev, res = run_cell("tiny-mla-serve", seconds=1.5)
    assert res["correct"], res["checks"]
    assert len(res["obs"]["gaps"]) > 20
    stamped = [a for n, _t, _d, a in res["obs"]["spans"]
               if n == "retire" and "experts_reached" in a]
    assert len(stamped) >= 2
    metrics = runner.per_layer_metrics(cell, res, dev, bench_dir)
    assert set(metrics) == {"compile_s", "prefill_share.serve",
                            "batch_occupancy.serve",
                            "expert_load_max_over_mean.serve",
                            "experts_reached_share.serve"}
    # 4 held experts: the busiest has between the mean and all of the load
    assert 1.0 <= metrics["expert_load_max_over_mean.serve"]["value"] <= 4.0
    assert 0 < metrics["experts_reached_share.serve"]["value"] <= 100
    seen = cell["model"]["family"].expert_counters(res["obs"])
    assert seen["decode_ticks_counted"] > 0
    assert seen["experts_reached"] <= (seen["decode_ticks_counted"]
                                       * 2 * 4)      # layers x held
    assert 0 < seen["prefill_expert_assignments"] < seen["expert_assignments"]


def test_train_cell_is_correct(run_cell):
    """The block's forward and ``jax.grad`` through ``Trainer.fit`` against
    the reference's three steps: losses, first gradient and the parameters'
    change, leaf by leaf (the router and the held experts among them)."""
    cell, _dev, res = run_cell("tiny-mla-train")
    assert res["correct"], res["checks"]
    leaves = set(res["obs"]["readings"]["ref"]["grad_norm"])
    assert {"L0.router.w", "L1.experts.w_down", "L0.kv_b.w", "L1.q_norm.scale",
            "L0.shared.w_up", "embed"} <= leaves


def _drop_the_shared_experts_output(sched):
    """The planted fault: an expert's output left out, in every layer.  The
    shared expert, which every token meets: at the toy's size one ROUTED
    expert of four moves the first choice of no sampled token (readings 0 to
    1.6e-5 over four runs; all four routed experts left out read 2e-3 to
    6e-3), the shared expert reads 0.03 to 0.045 against the toy's limit 2e-4
    (the float32 toy itself reads 0 to 2e-5, its fp8 control 2e-3 to 5e-3)."""
    import jax.numpy as jnp

    for blk in sched.server.params["blocks"]:
        shared = blk["moe"]["shared"]
        shared["w_out"] = jnp.zeros_like(shared["w_out"])


def test_an_experts_output_left_out_is_not_correct(run_cell):
    res = run_cell("tiny-mla-serve", seconds=1.5,
                   tamper=_drop_the_shared_experts_output)[2]
    assert len(res["obs"]["gaps"]) > 20, "too few served tokens to judge"
    assert not res["correct"]
    assert failed(res) == ["served_gap_mean_sigma"]
    assert res["obs"]["gaps"].mean() > 10 * 1e-3


def test_control_fp8_serving_is_not_correct(run_cell):
    """The reference computed with fp8 operands (the router's among them)
    puts first tokens that the float32 reference ranks further down than the
    limit allows; what the program served lies within it."""
    import jax

    from benchmark.harness import check
    from benchmark.reference import control
    from benchmark.reference import serve as ref_serve

    cell, _dev, res = run_cell("tiny-mla-serve", seconds=1.0)
    assert res["correct"]
    seqs = [toks for _p, toks in res["obs"]["sample"]]
    plens = [p for p, _toks in res["obs"]["sample"]]
    ref, _ = ref_serve.generated_logits(cell["model"], 7, seqs, plens,
                                        pad_to=16)
    low, _ = ref_serve.generated_logits(cell["model"], 7, seqs, plens,
                                        pad_to=16, quant=control.fp8_cast)
    gaps = check.served_gap(ref, jax.device_get(low.argmax(-1)))
    verdict = check.serve_checks(0, gaps, cell["limits"])
    assert [c["name"] for c in verdict if not c["ok"]] \
        == ["served_gap_mean_sigma"]
    assert gaps.mean() > 3 * res["obs"]["gaps"].mean()


def test_the_four_shares_add_up_to_the_uncut_layer(bench_dir):
    """The share test: the routed parts that the four chips of the
    deployment compute (experts 0-1, 2-3, 4-5, 6-7 of the toy's 8) plus the
    shared expert, counted once, equal what the uncut reference layer gives
    with all 8 experts.  float32 on the CPU: the sums differ in order only,
    so 1e-5 of the output's scale; a dropped assignment would move it by a
    whole expert's output (about 0.1 of the scale)."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import common, weights

    config = json.loads((bench_dir / "configs" / "tiny-mla-moe.json")
                        .read_text())
    whole = common.model_of({**config, "mapping": {
        **config["mapping"], "experts_first": 0, "experts_held": 8}},
        bench_dir)
    fam = whole["family"]
    p = {k: v.astype(jnp.float32)
         for k, v in weights.Maker(whole, 11).layer(0).items()}
    y = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        shared = fam.gated(y, p["shared.w_gate"], p["shared.w_up"],
                           p["shared.w_down"])
        uncut = shared + fam.routed(whole, p, y)
        parts = shared
        for first in (0, 2, 4, 6):
            share = {**whole, "experts_first": first, "experts_held": 2}
            mine = {**p, **{n: p[n][first:first + 2] for n in fam.EXPERTS}}
            parts = parts + fam.routed(share, mine, y)
    scale = float(jnp.abs(uncut).max())
    assert float(jnp.abs(parts - uncut).max()) <= 1e-5 * scale
    # and every token's weights over all 8 sum to 1 (top-2, renormalised)
    w = fam.combine_weights(whole, p, y)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-6)
    assert int((np.asarray(w) > 0).sum(-1).max()) == 2


def test_counts_at_the_published_widths_by_hand():
    """28.05 M in attention, 25.17 M an expert (routed or shared), 0.52 M in
    the router, 859.1 M a layer with 32 experts held, 640 bytes a cached
    token and layer, 5.42 B parameters in 6 layers and a quarter of the
    vocabulary."""
    from benchmark.harness import weights
    from benchmark.reducers import counts

    m = real_model()
    fam = m["family"]
    attn = (4096 * 1024 + 1024 * 32 * 128 + 4096 * (256 + 64)
            + 256 * 32 * (64 + 128) + 32 * 128 * 4096)
    expert, router = 3 * 4096 * 2048, 4096 * 128
    assert attn == 28_049_408 and expert == 25_165_824 and router == 524_288
    assert fam._size(m, fam.ATTENTION) == attn
    assert fam.expert_params(m) == fam._size(m, fam.SHARED) == expert
    norms = 2 * 4096 + 1024 + 256
    layer = attn + expert + router + 32 * expert + norms
    assert round(layer / 1e6, 1) == 859.1
    total = 6 * layer + 2 * 32768 * 4096 + 4096
    assert weights.n_params(m) == total and round(total / 1e9, 2) == 5.42
    # a token meets 4 x 32 / 128 = 1 routed expert in expectation
    assert counts.matmul_params(m) == 6 * (attn + expert + router + expert) \
        + 4096 * 32768
    assert fam.attention_flops(m, 1000) == 6 * 2 * 32 * 256 * 1000
    assert counts.kv_bytes_per_token(m) == 6 * 640
    # decode reads everything outside the routed experts and the embedding
    # table, plus the experts the ticks reached
    fixed = total - 32768 * 4096 - 6 * 32 * expert
    assert counts.weight_bytes(m) == (fixed + 6 * 32 * expert) * 2
    spans = [("retire", 0.0, 0.0, {"tick": 1, "decode_ticks_counted": 10,
                                   "experts_reached": 100}),
             ("retire", 1.0, 0.0, {"tick": 9, "decode_ticks_counted": 20,
                                   "experts_reached": 1300})]
    assert counts.weight_bytes(m, {"spans": spans}) \
        == (fixed + 120 * expert) * 2


def test_rooflines_pair_counters_and_device_time_of_one_stretch():
    """The two roofline rows by hand, on a hand-made trace.  Between the two
    stamped ``retire`` annotations inside the trace lie 10 decode ticks that
    reached 1200 experts and 10 ms under ``moe_experts`` in each: 120 x
    50.33 MB over 819 GB/s = 7.375 ms a tick, 73.75 %; and 10 prefill chunks
    of 6000 assignments and 20 ms each: 6000 x 2 x 25.17 M operations over
    197 TFLOP/s = 1.533 ms, 7.66 %.  What ran before the first stamp or
    after the last is left out on both sides; an unstamped ``retire`` (a tick
    that finished nothing) carries no reading."""
    from benchmark.reducers import experts

    m = real_model()
    cell, dev = {"model": m}, {"kind": "TPU v5 lite"}
    ms = 1_000_000
    step = "jit(step)/ffn/moe_experts/gmm:"
    pre = "jit(prefill)/ffn/moe_experts/gmm:"
    ops, modules, marks, spans = [], [], [], []
    attrs = lambda i: {"tick": i, "decode_ticks_counted": 10 * i,    # noqa: E731
                       "experts_reached": 100 + 1200 * i,
                       "expert_assignments": 2 * 6000 * 10 * i,
                       "expert_tokens_max": 6000 * 10 * i,
                       "prefill_expert_assignments": 500 + 60000 * i,
                       "prefill_chunks_counted": 10 * i}
    t, unix0 = 0, 1_700_000_000.0
    for i in range(4):              # stretches of 10 ticks, a stamp after each
        for _ in range(10):
            modules += [("jit_prefill(1)", t, 30 * ms),
                        ("jit_step(2)", t + 30 * ms, 15 * ms)]
            ops += [(pre, t, 20 * ms), (step, t + 30 * ms, 10 * ms),
                    ("jit(step)/attn_core/x:", t + 40 * ms, 5 * ms)]
            t += 50 * ms
        marks.append(("retire", t, t + ms))
        spans.append(("retire", unix0 + t / 1e9, 0.001, attrs(i + 1)))
        t += 2 * ms
    # the listener saw the whole window: a stamp before the trace began, an
    # unstamped retire inside it
    spans = ([("retire", unix0 - 3.0, 0.001, attrs(0))] + spans[:2]
             + [("retire", spans[1][1] + 0.0005, 0.0001, {"tick": 99})]
             + spans[2:])
    marks = marks[:2] + [("retire", marks[1][1] + ms // 2,
                          marks[1][1] + ms)] + marks[2:]
    from benchmark.reducers import scopes

    obs = {"spans": spans,
           "_scopes": {"/device:TPU:0": {
               "ops": ops, "modules": modules,
               "self": scopes.self_times(ops)}},
           "_host_phases": {"events": sorted(marks, key=lambda e: e[1]),
                            "chips": []}}
    delta, (t0, t1) = experts.traced_counters(obs, cell)
    assert delta["decode_ticks_counted"] == 30 and t1 - t0 == 3 * 502 * ms
    hbm = experts.hbm_share(obs, cell, dev, "moe_experts", "jit_step")
    assert hbm == pytest.approx(100 * 120 * 50_331_648 / 819e9 * 1e3 / 10.0)
    mxu = experts.mxu_share(obs, cell, dev, "moe_experts", "jit_prefill")
    assert mxu == pytest.approx(
        100 * 6000 * 2 * 25_165_824 / 197e12 * 1e3 / 20.0)
    # the window's rows read the first and last stamp of the whole window
    assert experts.reached_share(obs, cell, dev) \
        == pytest.approx(100 * 4800 / (40 * 6 * 32))
    assert experts.load_max_over_mean(obs, cell, dev) \
        == pytest.approx(240000 / (480000 / 32))
    bare = {"spans": [("retire", 0.0, 0.0, {"tick": 1})], "_scopes": None,
            "_host_phases": None}
    for fn in (experts.reached_share, experts.load_max_over_mean):
        assert fn(bare, cell, dev) is None
    assert experts.hbm_share(bare, cell, dev, "moe_experts", "jit_step") \
        is None


def test_the_real_configuration_keeps_every_published_width():
    """Every key of the catalog's row is at the top level of the file under
    its own name and value, but the three in ``reduced``; the published
    values are kept whole beside them; nothing in ``reduced`` is a width."""
    config = json.loads((BENCH / "configs" / "mistral-small-4-119b-ep4.json")
                        .read_text())
    pub = config["published"]
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    for key, value in pub.items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (6, 32, 32768)
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (36, 128, 131072)
    assert set(config["reduced_from"]) == set(config["reduced"])
    assert {"router_score", "softmax_scale", "weights"} <= set(
        config["assumed"])
    assert any("vision tower" in d for d in config["departures"])
    m = real_model()
    assert (m["experts_total"], m["experts_held"], m["top_k"]) == (128, 32, 4)
