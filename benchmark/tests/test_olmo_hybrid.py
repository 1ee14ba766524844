"""Family ``olmo_hybrid`` (Gated DeltaNet layers in place of attention in three
layers of four, per-stream recurrent state in those layers alone, pages in the
full layers alone, post-norm blocks) at a toy size on the CPU: through the
unmodified ``harness/serve_closed_loop.py``, the fp8 control on a sample fixed
by count, a planted stale state, the five new per-layer rows on a written
trace, the counts at the published widths by hand, and the reference's rule
one step a token.

The toy (``tests/data/configs/tiny-olmo-hybrid.json``) is float32 throughout,
so its limit is tight; its chunks are 16 wide, so that the toys' 20 to 60
positions carry the state across several chunks.
"""

import json

import numpy as np
import pytest

from conftest import BENCH

import xspace_writer

CELL = "olmohybrid-7b-pp2-serve-chat"


def real_cell(name=CELL):
    from benchmark.harness import common

    return common.load_cell(name)


def test_serve_cell_is_correct_and_stamps_the_state_counters(run_cell,
                                                             bench_dir):
    """Chunked prefill from a carried state, then decode beside strangers,
    against the reference's full forward pass (its rule one step a token);
    the counters count the six delta-rule layers and ride the ``retire``
    spans."""
    from benchmark import run as runner

    cell, dev, res = run_cell("tiny-olmo-serve", seconds=1.5)
    assert res["correct"], res["checks"]
    assert len(res["obs"]["gaps"]) > 20
    stamped = [a for n, _t, _d, a in res["obs"]["spans"]
               if n == "retire" and "ssm_state_updates" in a]
    assert len(stamped) >= 2
    first, last = stamped[0], stamped[-1]
    ticks = last["decode_ticks_counted"] - first["decode_ticks_counted"]
    rows = last["ssm_state_updates"] - first["ssm_state_updates"]
    # 6 delta-rule layers, at most 4 decoding streams a tick
    assert ticks > 0 and 0 < rows <= 6 * 4 * ticks and rows % 6 == 0
    assert (last["ssm_prefill_tokens"] - first["ssm_prefill_tokens"]) % 6 \
        == 0
    metrics = runner.per_layer_metrics(cell, res, dev, bench_dir)
    assert set(metrics) == {"compile_s", "prefill_share.serve",
                            "batch_occupancy.serve"}


@pytest.fixture(scope="module")
def fixed_sample(bench_dir):
    """The toy cell, and prompts with greedy continuations under its float32
    reference at seed 7: the sample both controls are judged on, fixed by
    count (six requests, 56 tokens)."""
    from benchmark.harness import common

    cell = common.load_cell("tiny-olmo-serve", bench_dir)
    return (cell, *_fixed_sample(cell["model"], 7))


def _fixed_sample(model, seed, sizes=((40, 9), (17, 12), (58, 6), (9, 11),
                                      (33, 8), (26, 10))):
    from benchmark.reference import serve as ref_serve

    rng = np.random.default_rng([seed, 17])
    seqs, plens = [], []
    for p, n in sizes:
        seq = rng.integers(0, model["vocab_size"], size=p).tolist()
        for _ in range(n):
            logits, _ = ref_serve.generated_logits(
                model, seed, [seq + [0]], [len(seq)], pad_to=16)
            seq.append(int(np.asarray(logits)[0].argmax()))
        seqs.append(seq), plens.append(p)
    return seqs, plens


def test_control_fp8_serving_is_not_correct(fixed_sample):
    """The reference computed with fp8 operands in every projection (the
    delta-rule layers' two among them; the rule stays float32) puts tokens
    that the float32 reference ranks further down than the toy's limit
    allows; the float32 reference's own tokens read 0."""
    import jax

    from benchmark.harness import check
    from benchmark.reference import control
    from benchmark.reference import serve as ref_serve

    cell, seqs, plens = fixed_sample
    model = cell["model"]
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


def test_control_bf16_state_serving_is_not_correct(fixed_sample):
    """The reference with its delta-rule state kept in bfloat16 between
    tokens (every projection and the rest of the rule float32): the state's
    precision alone moves the tokens further than the toy's limit allows."""
    import jax

    from benchmark.harness import check
    from benchmark.reference import serve as ref_serve

    cell, seqs, plens = fixed_sample
    model = cell["model"]
    ref, _ = ref_serve.generated_logits(model, 7, seqs, plens, pad_to=16)
    low, _ = ref_serve.generated_logits(
        {**model, "gdn_state_dtype": "bfloat16"}, 7, seqs, plens, pad_to=16)
    gaps = check.served_gap(ref, jax.device_get(low.argmax(-1)))
    verdict = check.serve_checks(0, gaps, cell["limits"])
    assert [c["name"] for c in verdict if not c["ok"]] \
        == ["served_gap_mean_sigma"]


def _stale_state(sched):
    """The planted fault: a stream admitted to a slot inherits the state and
    the convolution tail its last stream left (the reset is skipped)."""
    server = sched.server

    def skipped(slot, rid):
        server.state_rows[slot] = rid

    server._reset_state = skipped


def test_a_stale_state_is_not_correct(run_cell):
    res = run_cell("tiny-olmo-serve", seconds=1.5, tamper=_stale_state)[2]
    failed = sorted(c["name"] for c in res["checks"] if not c["ok"])
    assert not res["correct"] and failed == ["served_gap_mean_sigma"]


def test_the_reference_runs_the_rule_one_step_a_token():
    """The reference's linear layer holds one ``scan`` over the positions
    (as many steps as tokens) and no triangular solve: it shares no algebra
    with the program's chunked form."""
    import jax
    import jax.numpy as jnp

    fam = real_cell()["model"]["family"]
    b, t, h, dk, dv = 1, 37, 2, 4, 6
    args = [jnp.ones((b, t, h, dk)), jnp.ones((b, t, h, dk)) / 2,
            jnp.ones((b, t, h, dv)), jnp.full((b, t, h), 1.5),
            jnp.full((b, t, h), 0.9)]
    text = str(jax.make_jaxpr(fam.delta_rule)(*args))
    assert "scan[" in text and "length=37" in text
    assert "triangular_solve" not in text
    # by hand: one token, then a second that the first's state shapes
    q, k, v, beta, alpha = (np.asarray(a, np.float64) for a in args)
    s = np.zeros((h, dk, dv))
    want = []
    for i in range(2):
        kk = np.einsum("hi,hj->hij", k[0, i], k[0, i])
        keep = alpha[0, i][:, None, None] * (
            np.eye(dk) - beta[0, i][:, None, None] * kk)
        s = np.einsum("hij,hjv->hiv", keep, s) + beta[0, i][:, None, None] \
            * np.einsum("hi,hv->hiv", k[0, i], v[0, i])
        want.append(np.einsum("hkv,hk->hv", s, q[0, i]))
    got = np.asarray(fam.delta_rule(*(a[:, :2] for a in args)))[0]
    assert np.allclose(got, np.stack(want), rtol=1e-6)


def test_the_five_new_rows_read_a_written_trace(tmp_path):
    """The three scope rows and the two rooflines by hand, on a trace
    written in the profiler's own format.  Between the two stamped
    ``retire`` annotations inside the trace lie 10 decode ticks and 10
    chunks.  A tick spends 12 x 0.6 ms under ``gdn`` of which 12 x 0.4 under
    ``gdn_update``; it updates 48 streams x 12 layers of 2211840 B: (576 x 2
    x 2211840 B) over 819 GB/s = 3.111 ms against 4.8 ms, 64.8 %.  A chunk
    of 512 true columns spends 12 x 0.3 ms under ``gdn_scan``: its bytes
    (6144 x 46320 B of inputs and outputs + 12 x 2 x 2.21 MB of state =
    337.7 MB, 0.4123 ms) bound it, not its operations (6144 x 5.898 MFLOP
    over 197 TFLOP/s = 0.1840 ms): 11.45 %."""
    from benchmark.reducers import deltanet, scopes, ssm

    cell, dev = {"model": real_cell()["model"]}, {"kind": "TPU v5 lite"}
    us = 1000           # the writer's unit is ns; times below in us
    tick = "jit(step)/gdn/{}/x:"
    chunk = "jit(prefill)/gdn/{}/x:"
    ops, modules, marks, spans = [], [], [], []
    attrs = lambda i: {"tick": i, "decode_ticks_counted": 10 * i,  # noqa: E731
                       "prefill_chunks_counted": 3 + 10 * i,
                       "ssm_state_updates": 11 + 5760 * i,
                       "ssm_prefill_tokens": 61440 * i}
    t, unix0 = 0, 1_700_000_000.0
    for i in range(3):
        for _ in range(10):
            modules += [("jit_prefill(1)", t, 30_000 * us, {}),
                        ("jit_step(2)", t + 30_000 * us, 15_000 * us, {})]
            for layer in range(12):
                at = t + layer * 1_000 * us
                ops += [("%f = fusion()", at, 300 * us,
                         {"tf_op": chunk.format("gdn_scan")}),
                        ("%g = fusion()", at + 30_000 * us, 400 * us,
                         {"tf_op": tick.format("gdn_update")}),
                        ("%h = fusion()", at + 30_400 * us, 200 * us,
                         {"tf_op": tick.format("gdn_gate_norm")})]
            ops.append(("%m = fusion()", t + 43_000 * us, 1_000 * us,
                        {"tf_op": "jit(step)/ffn/dot_general:"}))
            t += 50_000 * us
        marks.append(("nnpt:retire", t, 1_000 * us, {}))
        marks.append(("nnpt:decode", t - 20_000 * us, 15_000 * us, {}))
        spans.append(("retire", unix0 + t / 1e9, 0.001, attrs(i + 1)))
        t += 2_000 * us
    path = tmp_path / "plugins" / "profile" / "x" / "t.xplane.pb"
    xspace_writer.write(path, [
        xspace_writer.plane("/device:TPU:0", {"XLA Ops": ops,
                                              "XLA Modules": modules}),
        xspace_writer.plane("/host:CPU", {"python3": sorted(
            marks, key=lambda m: m[1])})])

    class Traced:
        def trace_file(self):
            return path

    obs = {"spans": spans, "profiler": Traced()}
    per = lambda scope, module: scopes.scope_ms_per_module(      # noqa: E731
        obs, cell, dev, scope=scope, module=module)
    assert per("gdn", "jit_step") == pytest.approx(12 * 0.6)
    assert per("gdn_update", "jit_step") == pytest.approx(12 * 0.4)
    assert per("gdn_scan", "jit_prefill") == pytest.approx(12 * 0.3)
    got = ssm.state_roofline(obs, cell, dev, scope="gdn_update",
                             module="jit_step")
    assert got == pytest.approx(
        100 * (5760 * 2 * 2211840 / 819e9 * 1e3) / (10 * 4.8))
    assert 64.7 < got < 64.9
    model = cell["model"]
    moved = deltanet.scan_bytes(model, 61440, 120)
    per_token = (2 * 2880 + 5760) * 2 + 4 * (2 * 30 + 5760)
    assert per_token == 46320
    assert moved == 61440 * per_token + 120 * 2 * 2211840
    flops_ms = 61440 * 5898240 / 197e12 * 1e3
    bytes_ms = moved / 819e9 * 1e3
    assert bytes_ms > flops_ms
    got = deltanet.scan_roofline(obs, cell, dev, scope="gdn_scan",
                                 module="jit_prefill")
    assert got == pytest.approx(100 * bytes_ms / (10 * 3.6))
    assert 11.4 < got < 11.5
    for name, reducer, scope, module in (
            ("decode_gdn_ms.serve", "scopes:scope_ms_per_module", "gdn",
             "jit_step"),
            ("decode_gdn_update_ms.serve", "scopes:scope_ms_per_module",
             "gdn_update", "jit_step"),
            ("prefill_gdn_scan_ms.serve", "scopes:scope_ms_per_module",
             "gdn_scan", "jit_prefill"),
            ("gdn_state_roofline.serve", "ssm:state_roofline", "gdn_update",
             "jit_step"),
            ("gdn_scan_roofline.serve", "deltanet:scan_roofline", "gdn_scan",
             "jit_prefill")):
        spec = json.loads((BENCH / "metrics" / f"{name}.json").read_text())
        assert (spec["reducer"], spec["args"]) == (
            reducer, {"scope": scope, "module": module}), name
        assert spec["layer"] == "delta-rule mixer"
    # a program without the counters, or another family, reads nothing
    bare = {"spans": [("retire", 0.0, 0.0, {"tick": 1})], "_scopes": None,
            "_host_phases": None}
    assert deltanet.scan_roofline(bare, cell, dev, "x", "jit_prefill") is None
    falcon = {"model": real_cell("falconh1-34b-pp12-serve-chat")["model"]}
    assert deltanet.scan_roofline({"spans": spans, "profiler": Traced()},
                                  falcon, dev, "gdn_scan",
                                  "jit_prefill") is None


def test_counts_at_the_published_widths_by_hand():
    """88.70 M in a linear layer's six projections and its output, 88.75
    M with the convolutions and the rest of its mixer, 58.98 M in a full layer's
    attention, 126.81 M in every feed-forward, 832.5 M a period, 770.7 M
    of vocabulary, 4.10 B parameters in 16 layers (4100788944); the state of a layer and
    stream, 2211840 B; 61440 B of keys and values a position in the 4 full
    layers alone."""
    from benchmark.harness import weights
    from benchmark.reducers import counts

    m = real_cell()["model"]
    fam = m["family"]
    assert fam.attention_pattern(m) == "DDDG" * 4
    assert (fam.n_linear(m), fam.n_full(m), fam.head_dim(m)) == (12, 4, 128)
    mix_in = 3840 * (2880 + 2880 + 5760 + 5760 + 30 + 30)
    mix_out = 5760 * 3840
    attn, ffn = 4 * 3840 * 3840, 3 * 3840 * 11008
    assert fam.gdn_widths(m) == (2880, 2880, 5760, 5760, 30, 30)
    assert fam._size(m, fam.MIXER_PROJ) == mix_in + mix_out
    assert fam._size(m, fam.ATTENTION) == attn
    assert fam._size(m, fam.FFN) == ffn
    mixer = mix_in + mix_out + 4 * 11520 + 30 + 30 + 192
    assert round((mix_in + mix_out) / 1e6, 2) == 88.70
    assert round(mixer / 1e6, 2) == 88.75 and round(attn / 1e6, 2) == 58.98
    assert round(ffn / 1e6, 2) == 126.81
    linear = mixer + ffn + 2 * 3840
    full = attn + 2 * 3840 + ffn + 2 * 3840
    assert round((3 * linear + full) / 1e6, 1) == 832.5
    vocab = 2 * 100352 * 3840
    assert round(vocab / 1e6, 1) == 770.7
    total = 12 * linear + 4 * full + vocab + 3840
    assert weights.n_params(m) == total and round(total / 1e9, 2) == 4.10
    assert counts.matmul_params(m) == (
        12 * (mix_in + mix_out) + 4 * attn + 16 * ffn + 3840 * 100352)
    assert fam.state_bytes(m) == 30 * 96 * 192 * 4 == 2_211_840
    assert counts.kv_bytes_per_token(m) == 4 * 2 * 30 * 128 * 2 == 61440
    rec = 30 * (4 * 64 * 96 + 2 * 64 * (96 + 192) + 6 * 96 * 192
                + 2 * 64 * 192)
    assert fam.recurrence_flops(m) == rec and round(rec / 1e6, 2) == 5.90
    per_key = 2 * 30 * 2 * 128
    assert fam.attention_flops(m, 100) == 4 * per_key * 100 + 12 * rec
    fixed = (total - 100352 * 3840) * 2
    assert counts.weight_bytes(m) == fixed
    obs = {"decode_stream_ticks": 6000, "decode_ticks": 100}
    assert counts.weight_bytes(m, obs) == fixed + 60 * 2 * 12 * 2_211_840


def test_the_real_configuration_keeps_every_published_width():
    """Every key of the catalog's row is at the top level of the file under
    its own name and value, but the depth; the published values are kept
    whole beside them; the cell's files say what its issue says."""
    config = json.loads((BENCH / "configs" / "olmo-hybrid-7b-pp2.json")
                        .read_text())
    pub = config["published"]
    assert config["reduced"] == ["num_hidden_layers"]
    for key, value in pub.items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], pub["num_hidden_layers"]) == (16, 32)
    assert (pub["hidden_size"], pub["num_attention_heads"],
            pub["num_key_value_heads"], pub["intermediate_size"],
            pub["vocab_size"], pub["linear_num_key_heads"],
            pub["linear_num_value_heads"], pub["linear_key_head_dim"],
            pub["linear_value_head_dim"], pub["linear_conv_kernel_dim"]) == (
        3840, 30, 30, 11008, 100352, 30, 30, 96, 192, 4)
    assert pub["rope_parameters"] == {"rope_theta": None}
    assert set(config["reduced_from"]) == set(config["reduced"])
    assert {"block_order", "qk_norm", "positions", "linear_layer",
            "state_precision", "weights"} <= set(config["assumed"])
    assert any("max_position_embeddings" in d for d in config["departures"])
    assert "2 stages of 16 layers" in config["deployment"]
    cell = real_cell()
    job = cell["job"]
    assert job["serve_config"] == {"slots": 64, "block_size": 16,
                                   "num_blocks": 4097, "max_len": 3072,
                                   "prefill_chunk": 512}
    chat = real_cell("falconh1-34b-pp12-serve-chat")["job"]
    for key in ("clients", "prompt_len", "output_len", "shape_seed",
                "shape_pool", "check_requests", "trace"):
        assert job[key] == chat[key], key
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    listed = {x["name"] for x in bench["per_layer"]
              if CELL in x.get("workloads", ())}
    assert listed == set(cell["per_layer"]) and len(listed) == 20


def test_a_program_without_delta_rule_layers_fails_at_once_by_name(
        monkeypatch):
    """The parent of the PR that brought the delta-rule layers cannot build
    the configuration: the adapter says so and stops before any weight is
    made."""
    from neural_networks_parallel_training_with_mpi_tpu import models

    fam = real_cell()["model"]["family"]

    def older(**kw):
        raise TypeError("TransformerConfig.__init__() got an unexpected "
                        "keyword argument 'qk_norm_whole'")

    monkeypatch.setattr(models, "TransformerConfig", older)
    with pytest.raises(SystemExit, match="family olmo_hybrid.*qk_norm_whole"):
        fam.transformer_config(real_cell()["model"])
