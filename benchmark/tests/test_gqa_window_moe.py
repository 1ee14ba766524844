"""Family ``gqa_window_moe`` (window and full attention layers in one model,
the per-head norm of q and k, a dense leading layer, the sigmoid router over a
held share of experts) at a toy size on the CPU: through the unmodified
``harness/serve_closed_loop.py``, the fp8 control on a sample fixed by count,
the five new per-layer rows on a hand-made trace, and the counts at the
published widths by hand.

The toy (``tests/data/configs/tiny-window-moe.json``) is float32 throughout, so
its limit is tight; its window is 8 over pages of 8 and chunks of 16, so that
the toys' 20 to 60 positions are several windows long.
"""

import json

import numpy as np
import pytest

from conftest import BENCH

CELL = "kexaone-236b-ep8-serve-longmix"


def real_cell():
    from benchmark.harness import common

    return common.load_cell(CELL)


def test_serve_cell_is_correct_and_stamps_both_kinds_of_counter(run_cell,
                                                                bench_dir):
    """Chunked prefill then decode through both kinds of cache against the
    reference's full forward pass; the attention counters ride the ``retire``
    spans beside the experts'."""
    from benchmark import run as runner

    cell, dev, res = run_cell("tiny-window-serve", seconds=1.5)
    assert res["correct"], res["checks"]
    assert len(res["obs"]["gaps"]) > 20
    stamped = [a for n, _t, _d, a in res["obs"]["spans"]
               if n == "retire" and "window_keys" in a]
    assert len(stamped) >= 2 and "experts_reached" in stamped[-1]
    seen = cell["model"]["family"].expert_counters(res["obs"])
    ticks = seen["decode_ticks_counted"]
    assert ticks > 0 and 0 < seen["window_keys"] <= 4 * 8 * 4 * ticks
    assert seen["full_keys"] > 0 and seen["window_blocks_held"] > 0
    # 4 sparse layers of the 5: the dense one reaches no expert
    assert seen["experts_reached"] <= ticks * 4 * 4
    metrics = runner.per_layer_metrics(cell, res, dev, bench_dir)
    # no device trace on the CPU: the five trace rows read nothing and are
    # left out, the counter rows read
    assert set(metrics) == {"compile_s", "prefill_share.serve",
                            "batch_occupancy.serve",
                            "expert_load_max_over_mean.serve",
                            "experts_reached_share.serve"}


def _fixed_sample(model, seed, sizes=((40, 9), (17, 12), (58, 6), (9, 11),
                                      (33, 8), (26, 10))):
    """Prompts with greedy continuations under the float32 reference: the
    sample the control is judged on, fixed by count (six requests, 56
    tokens), not by what a window's clock happened to complete."""
    from benchmark.reference import serve as ref_serve

    rng = np.random.default_rng([seed, 17])
    seqs, plens = [], []
    for p, n in sizes:
        seq = rng.integers(0, model["vocab_size"], size=p).tolist()
        for _ in range(n):      # one reference pass a token: greedy decode
            logits, _ = ref_serve.generated_logits(
                model, seed, [seq + [0]], [len(seq)], pad_to=16)
            seq.append(int(np.asarray(logits)[0].argmax()))
        seqs.append(seq), plens.append(p)
    return seqs, plens


def test_control_fp8_serving_is_not_correct(bench_dir):
    """The reference computed with fp8 operands (the router's among them)
    puts first tokens that the float32 reference ranks further down than the
    toy's limit allows; the float32 reference's own tokens read 0."""
    import jax

    from benchmark.harness import check, common
    from benchmark.reference import control
    from benchmark.reference import serve as ref_serve

    cell = common.load_cell("tiny-window-serve", bench_dir)
    model = cell["model"]
    seqs, plens = _fixed_sample(model, 7)
    ref, toks = ref_serve.generated_logits(model, 7, seqs, plens, pad_to=16)
    assert len(toks) == 56
    own = check.served_gap(ref, toks)
    assert check.serve_checks(0, own, cell["limits"])[1]["ok"]
    assert float(own.max()) == 0.0
    low, _ = ref_serve.generated_logits(model, 7, seqs, plens, pad_to=16,
                                        quant=control.fp8_cast)
    gaps = check.served_gap(ref, jax.device_get(low.argmax(-1)))
    verdict = check.serve_checks(0, gaps, cell["limits"])
    assert [c["name"] for c in verdict if not c["ok"]] \
        == ["served_gap_mean_sigma"]
    assert gaps.mean() > 3 * cell["limits"]["served_gap_mean_sigma"]


def test_the_five_new_rows_read_a_written_trace():
    """The four scope rows and the roofline by hand, on a hand-made trace.
    Between the two stamped ``retire`` annotations inside the trace lie 10
    decode ticks and 10 chunks; a tick spends 1.0 ms under ``attn_full`` and
    0.4 ms under ``attn_window`` (4 layers of 0.1) inside
    ``attn_core/.../paged_attention_fused``, a chunk 6 and 2.  The ticks read
    250000 full and 24576 window keys each: (274576 x 4096 B) over 819 GB/s
    = 1.3732 ms against 1.4 ms, 98.09 %."""
    from benchmark.reducers import attention, scopes

    cell = {"model": real_cell()["model"]}
    dev = {"kind": "TPU v5 lite"}
    ms = 1_000_000
    path = "jit({m})/attention/attn_core/{k}/paged_attention_fused/x:"
    ops, modules, marks, spans = [], [], [], []
    attrs = lambda i: {"tick": i, "decode_ticks_counted": 10 * i,    # noqa: E731
                       "experts_reached": 600 * i,
                       "full_keys": 7 + 2_500_000 * i,
                       "window_keys": 245_760 * i,
                       "full_blocks_held": 16_000 * i,
                       "window_blocks_held": 17_280 * i}
    t, unix0 = 0, 1_700_000_000.0
    for i in range(3):
        for _ in range(10):
            modules += [("jit_prefill(1)", t, 30 * ms),
                        ("jit_step(2)", t + 30 * ms, 15 * ms)]
            ops += [(path.format(m="prefill", k="attn_full"), t, 6 * ms),
                    (path.format(m="prefill", k="attn_window"), t + 6 * ms,
                     2 * ms),
                    (path.format(m="step", k="attn_full"), t + 30 * ms, ms),
                    ("jit(step)/ffn/moe_experts/gmm:", t + 32 * ms, 9 * ms)]
            ops += [(path.format(m="step", k="attn_window"),
                     t + 41 * ms + j * ms // 10, ms // 10) for j in range(4)]
            t += 50 * ms
        marks.append(("retire", t, t + ms))
        spans.append(("retire", unix0 + t / 1e9, 0.001, attrs(i + 1)))
        t += 2 * ms
    obs = {"spans": spans,
           "_scopes": {"/device:TPU:0": {
               "ops": ops, "modules": modules,
               "self": scopes.self_times(ops)}},
           "_host_phases": {"events": marks, "chips": []}}
    per = lambda scope, module: scopes.scope_ms_per_module(      # noqa: E731
        obs, cell, dev, scope=scope, module=module)
    assert per("attn_full", "jit_step") == pytest.approx(1.0)
    assert per("attn_window", "jit_step") == pytest.approx(0.4)
    assert per("attn_full", "jit_prefill") == pytest.approx(6.0)
    assert per("attn_window", "jit_prefill") == pytest.approx(2.0)
    assert per("attn_core", "jit_step") == pytest.approx(1.4)
    got = attention.paged_roofline(obs, cell, dev,
                                   scope="paged_attention_fused",
                                   module="jit_step")
    keys = 2_500_000 + 245_760
    assert got == pytest.approx(
        100 * (2 * keys * 4096 / 819e9 * 1e3) / (2 * 10 * 1.4))
    assert 98.0 < got < 98.2
    # the metric files say the same scopes, and a program without the
    # counters (or a run without a trace) reads nothing and does not raise
    for name, scope, module in (
            ("decode_attn_full_ms.serve", "attn_full", "jit_step"),
            ("decode_attn_window_ms.serve", "attn_window", "jit_step"),
            ("prefill_attn_full_ms.serve", "attn_full", "jit_prefill"),
            ("prefill_attn_window_ms.serve", "attn_window", "jit_prefill"),
            ("paged_attention_roofline.serve", "paged_attention_fused",
             "jit_step")):
        spec = json.loads((BENCH / "metrics" / f"{name}.json").read_text())
        assert spec["args"] == {"scope": scope, "module": module}, name
    bare = {"spans": [("retire", 0.0, 0.0, {"tick": 1})], "_scopes": None,
            "_host_phases": None}
    assert attention.paged_roofline(bare, cell, dev, "x", "jit_step") is None
    older = dict(obs, spans=[(n, t_, d, {k: v for k, v in a.items()
                                         if "keys" not in k})
                             for n, t_, d, a in spans])
    older.pop("_traced_counters", None)
    assert attention.paged_roofline(older, cell, dev,
                                    "paged_attention_fused",
                                    "jit_step") is None


def test_counts_at_the_published_widths_by_hand():
    """113.25 M in attention, 339.7 M in the dense layer's feed-forward,
    37.75 M an expert (routed or shared), 0.79 M in the router, 755.8 M a
    sparse layer with 16 experts held, 3.71 B parameters in 5 layers and an
    eighth of the vocabulary; the window terms of the counts."""
    from benchmark.harness import weights
    from benchmark.reducers import counts

    m = real_cell()["model"]
    fam = m["family"]
    attn = 6144 * (8192 + 2 * 1024) + 8192 * 6144
    dense, expert, router = 3 * 6144 * 18432, 3 * 6144 * 2048, 6144 * 128
    assert (attn, dense, expert, router) == (
        113_246_208, 339_738_624, 37_748_736, 786_432)
    assert fam._size(m, fam.ATTENTION) == attn
    assert fam._size(m, fam.FFN) == dense
    assert fam.expert_params(m) == fam._size(m, fam.SHARED) == expert
    norms = 2 * 6144 + 2 * 128
    layer0 = attn + dense + norms
    sparse = attn + 17 * expert + router + 128 + norms
    assert round(layer0 / 1e6, 1) == 453.0 and round(sparse / 1e6, 1) == 755.8
    total = layer0 + 4 * sparse + 2 * 19200 * 6144 + 6144
    assert weights.n_params(m) == total and round(total / 1e9, 2) == 3.71
    assert fam.attention_kinds(m) == [fam.WINDOW] * 3 + [fam.FULL,
                                                         fam.WINDOW]
    assert fam.ffn_kinds(m) == [fam.DENSE] + [fam.SPARSE] * 4
    assert (fam.n_window(m), fam.n_sparse(m), fam.first_dense(m)) == (4, 4, 1)
    assert fam.attention_pattern(m) == "LLLGL"
    # a token meets 8 x 16 / 128 = 1 routed expert in expectation in each
    # sparse layer, the dense width in layer 0
    assert counts.matmul_params(m) == (
        5 * attn + dense + 4 * (expert + router + expert) + 6144 * 19200)
    # scores and values: 64 heads x 2 x 128 lanes x 2 a key; the full layer
    # counts every key, the four window layers 128 at most
    per_key = 2 * 64 * 256
    assert fam.attention_flops(m, 100) == per_key * 5 * 100
    assert fam.attention_flops(m, 5000) == per_key * (5000 + 4 * 128)
    both = fam.attention_flops(m, np.array([64, 128, 129, 17408]))
    assert both.tolist() == [per_key * 5 * 64, per_key * 5 * 128,
                             per_key * (129 + 512), per_key * (17408 + 512)]
    # a request of 1000 + 24: the window layers stop growing at 128
    ctx = np.arange(1, 1025)
    assert counts.request_flops(m, 1000, 24) == pytest.approx(
        2.0 * counts.matmul_params(m) * 1024
        + per_key * (ctx.sum() + 4 * np.minimum(ctx, 128).sum()))
    # the cache: the full layer's row alone (a lower bound), 4096 B
    assert fam.kv_row_bytes(m) == 4096
    assert counts.kv_bytes_per_token(m) == 4096
    # decode reads everything outside the routed experts and the embedding
    # table, plus the experts the ticks reached in the 4 sparse layers
    fixed = total - 19200 * 6144 - 4 * 16 * expert
    assert counts.weight_bytes(m) == (fixed + 4 * 16 * expert) * 2
    spans = [("retire", 0.0, 0.0, {"tick": 1, "decode_ticks_counted": 10,
                                   "experts_reached": 100}),
             ("retire", 1.0, 0.0, {"tick": 9, "decode_ticks_counted": 20,
                                   "experts_reached": 700})]
    assert counts.weight_bytes(m, {"spans": spans}) \
        == (fixed + 60 * expert) * 2


def test_the_real_configuration_keeps_every_published_width():
    """Every key of the catalog's row is at the top level of the file under
    its own name and value, but the three in ``reduced``; the published
    values are kept whole beside them; nothing in ``reduced`` is a width; the
    cell's files say what ISSUE 33 says."""
    config = json.loads((BENCH / "configs" / "k-exaone-236b-ep8.json")
                        .read_text())
    pub = config["published"]
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    for key, value in pub.items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 16, 19200)
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (48, 128, 153600)
    assert (pub["hidden_size"], pub["num_attention_heads"],
            pub["num_key_value_heads"], pub["head_dim"],
            pub["sliding_window"], pub["intermediate_size"],
            pub["moe_intermediate_size"], pub["num_experts_per_tok"],
            pub["routed_scaling_factor"]) == (6144, 64, 8, 128, 128, 18432,
                                              2048, 8, 2.5)
    assert set(config["reduced_from"]) == set(config["reduced"])
    assert {"block_order", "qk_norm", "positions", "window_edges", "router",
            "router_bias", "weights"} <= set(config["assumed"])
    assert any("multi-token-prediction" in d for d in config["departures"])
    assert "8 chips share each layer" in config["deployment"]
    cell = real_cell()
    m, job = cell["model"], cell["job"]
    assert (m["experts_total"], m["experts_held"], m["top_k"],
            m["routed_scale"]) == (128, 16, 8, 2.5)
    assert job["serve_config"] == {"slots": 48, "block_size": 16,
                                   "num_blocks": 48 * 1088 + 1,
                                   "max_len": 17408, "prefill_chunk": 1024}
    assert (job["clients"], job["shape_seed"], job["shape_pool"],
            job["check_requests"]) == (48, 20261003, 512, 4)
    assert job["prompt_len"] == {"dist": "lognormal", "median": 4096,
                                 "sigma": 0.7, "min": 1024, "max": 16384}
    assert job["output_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.6, "min": 64, "max": 1024}
    assert "decode_gather_ms.serve" not in cell["per_layer"]
    assert "decode_mla_absorb_ms.serve" not in cell["per_layer"]
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    assert len(bench["workloads"]) == 5
    assert [w["chips"] for w in bench["workloads"]].count(4) == 1
    listed = {x["name"] for x in bench["per_layer"]
              if CELL in x.get("workloads", ())}
    assert listed == set(cell["per_layer"])


def test_a_program_without_the_kinds_fails_at_once_by_name(monkeypatch):
    """The parent of the PR that brought the layer kinds cannot build the
    configuration: the adapter says so and stops before any weight is
    made."""
    from neural_networks_parallel_training_with_mpi_tpu import models

    fam = real_cell()["model"]["family"]

    def older(**kw):
        raise TypeError("TransformerConfig.__init__() got an unexpected "
                        "keyword argument 'head_width'")

    monkeypatch.setattr(models, "TransformerConfig", older)
    with pytest.raises(SystemExit, match="family gqa_window_moe.*head_width"):
        fam.transformer_config(real_cell()["model"])
