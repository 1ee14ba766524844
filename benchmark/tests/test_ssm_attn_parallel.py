"""Family ``ssm_attn_parallel`` (a Mamba-2 mixer beside grouped-query attention
in every block, per-stream recurrent state next to the paged pool, the model's
multipliers) at a toy size on the CPU: through the unmodified
``harness/serve_closed_loop.py``, the fp8 control on a sample fixed by count,
a planted stale state, the five new per-layer rows on a written trace, and the
counts at the published widths by hand.

The toy (``tests/data/configs/tiny-ssm-attn.json``) is float32 throughout, so
its limit is tight; its recurrence is tiled at 8 and its chunks are 16 wide, so
that the toys' 20 to 60 positions cross several tiles and chunks.
"""

import json

import numpy as np
import pytest

from conftest import BENCH

import xspace_writer

CELL = "falconh1-34b-pp12-serve-chat"


def real_cell(name=CELL):
    from benchmark.harness import common

    return common.load_cell(name)


def test_serve_cell_is_correct_and_stamps_the_mixers_counters(run_cell,
                                                              bench_dir):
    """Chunked prefill from a carried state, then decode beside strangers,
    against the reference's full forward pass (its recurrence one step a
    token); the mixers' counters ride the ``retire`` spans."""
    from benchmark import run as runner

    cell, dev, res = run_cell("tiny-ssm-serve", seconds=1.5)
    assert res["correct"], res["checks"]
    assert len(res["obs"]["gaps"]) > 20
    stamped = [a for n, _t, _d, a in res["obs"]["spans"]
               if n == "retire" and "ssm_state_updates" in a]
    assert len(stamped) >= 2
    first, last = stamped[0], stamped[-1]
    ticks = last["decode_ticks_counted"] - first["decode_ticks_counted"]
    rows = last["ssm_state_updates"] - first["ssm_state_updates"]
    # 3 mixer layers, at most 4 decoding streams a tick
    assert ticks > 0 and 0 < rows <= 3 * 4 * ticks and rows % 3 == 0
    assert last["ssm_prefill_tokens"] > first["ssm_prefill_tokens"]
    assert last["prefill_chunks_counted"] > first["prefill_chunks_counted"]
    metrics = runner.per_layer_metrics(cell, res, dev, bench_dir)
    # no device trace on the CPU: the trace rows read nothing and are left
    # out, the counter rows read
    assert set(metrics) == {"compile_s", "prefill_share.serve",
                            "batch_occupancy.serve"}


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
    """The reference computed with fp8 operands in every projection (the
    mixer's two among them; the recurrence stays float32) puts first tokens
    that the float32 reference ranks further down than the toy's limit
    allows; the float32 reference's own tokens read 0."""
    import jax

    from benchmark.harness import check, common
    from benchmark.reference import control
    from benchmark.reference import serve as ref_serve

    cell = common.load_cell("tiny-ssm-serve", bench_dir)
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


def _stale_state(sched):
    """The planted fault: a stream admitted to a slot inherits the state and
    the convolution tail its last stream left (the reset is skipped)."""
    server = sched.server

    def skipped(slot, rid):
        server.state_rows[slot] = rid

    server._reset_state = skipped


def test_a_stale_state_is_not_correct(run_cell):
    res = run_cell("tiny-ssm-serve", seconds=1.5, tamper=_stale_state)[2]
    failed = sorted(c["name"] for c in res["checks"] if not c["ok"])
    assert not res["correct"] and failed == ["served_gap_mean_sigma"]


def test_the_five_new_rows_read_a_written_trace(tmp_path):
    """The three scope rows and the two rooflines by hand, on a trace
    written in the profiler's own format.  Between the two stamped ``retire``
    annotations inside the trace lie 10 decode ticks and 10 chunks.  A tick
    spends 6 x 0.8 ms under ``ssm`` of which 6 x 0.5 under ``ssm_update``;
    it updates 64 x 6 rows of 4.19 MB: (384 x 2 x 4194304 B) over 819 GB/s =
    3.933 ms against 3.0 ms would pass 100, so the hand-made tick decodes 40
    streams: 240 rows, 2.458 ms, 81.9 %.  A chunk of 512 true columns spends
    6 x 0.4 ms under ``ssm_scan``: its bytes (3072 x 26752 B of inputs and
    outputs + 6 x 2 x 4.19 MB of state = 132.5 MB, 0.1618 ms) bound it, not
    its operations (3072 x 5.374 MFLOP over 197 TFLOP/s = 0.0838 ms): 6.74
    %."""
    from benchmark.reducers import scopes, ssm

    cell, dev = {"model": real_cell()["model"]}, {"kind": "TPU v5 lite"}
    us = 1000           # the writer's unit is ns; times below in us
    tick = "jit(step)/ssm/{}/x:"
    chunk = "jit(prefill)/ssm/{}/x:"
    ops, modules, marks, spans = [], [], [], []
    attrs = lambda i: {"tick": i, "decode_ticks_counted": 10 * i,  # noqa: E731
                       "prefill_chunks_counted": 3 + 10 * i,
                       "ssm_state_updates": 11 + 2400 * i,
                       "ssm_prefill_tokens": 30720 * i}
    t, unix0 = 0, 1_700_000_000.0
    for i in range(3):
        for _ in range(10):
            modules += [("jit_prefill(1)", t, 30_000 * us, {}),
                        ("jit_step(2)", t + 30_000 * us, 15_000 * us, {})]
            for layer in range(6):
                at = t + layer * 1_000 * us
                ops += [("%f = fusion()", at, 400 * us,
                         {"tf_op": chunk.format("ssm_scan")}),
                        ("%g = fusion()", at + 30_000 * us, 500 * us,
                         {"tf_op": tick.format("ssm_update")}),
                        ("%h = fusion()", at + 30_500 * us, 300 * us,
                         {"tf_op": tick.format("ssm_gate_norm")})]
            ops.append(("%m = fusion()", t + 40_000 * us, 4_000 * us,
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
    assert per("ssm", "jit_step") == pytest.approx(6 * 0.8)
    assert per("ssm_update", "jit_step") == pytest.approx(6 * 0.5)
    assert per("ssm_scan", "jit_prefill") == pytest.approx(6 * 0.4)
    assert per("ssm_scan", "jit_step") == 0.0    # the scope is a chunk's
    got = ssm.state_roofline(obs, cell, dev, scope="ssm_update",
                             module="jit_step")
    assert got == pytest.approx(
        100 * (2400 * 2 * 4194304 / 819e9 * 1e3) / (10 * 3.0))
    assert 81.8 < got < 82.0
    model = cell["model"]
    moved = ssm.scan_bytes(model, 30720, 60)
    assert moved == 30720 * (5120 * 2 + 4 * (32 + 4096)) + 60 * 2 * 4194304
    flops_ms = 30720 * 5373952 / 197e12 * 1e3
    bytes_ms = moved / 819e9 * 1e3
    assert bytes_ms > flops_ms
    got = ssm.scan_roofline(obs, cell, dev, scope="ssm_scan",
                            module="jit_prefill")
    assert got == pytest.approx(100 * bytes_ms / (10 * 2.4))
    assert 6.7 < got < 6.8
    # the metric files say the same scopes, and a program without the
    # counters (or a run without a trace) reads nothing and does not raise
    for name, reducer, scope, module in (
            ("decode_ssm_ms.serve", "scopes:scope_ms_per_module", "ssm",
             "jit_step"),
            ("decode_ssm_update_ms.serve", "scopes:scope_ms_per_module",
             "ssm_update", "jit_step"),
            ("prefill_ssm_scan_ms.serve", "scopes:scope_ms_per_module",
             "ssm_scan", "jit_prefill"),
            ("ssm_state_roofline.serve", "ssm:state_roofline", "ssm_update",
             "jit_step"),
            ("ssm_scan_roofline.serve", "ssm:scan_roofline", "ssm_scan",
             "jit_prefill")):
        spec = json.loads((BENCH / "metrics" / f"{name}.json").read_text())
        assert (spec["reducer"], spec["args"]) == (
            reducer, {"scope": scope, "module": module}), name
        assert spec["layer"] == "state-space mixer"
    bare = {"spans": [("retire", 0.0, 0.0, {"tick": 1})], "_scopes": None,
            "_host_phases": None}
    assert ssm.state_roofline(bare, cell, dev, "x", "jit_step") is None
    assert ssm.scan_roofline(bare, cell, dev, "x", "jit_prefill") is None
    older = {"spans": [(n, t_, d, {k: v for k, v in a.items()
                                   if not k.startswith("ssm_")})
                       for n, t_, d, a in spans], "profiler": Traced()}
    assert ssm.state_roofline(older, cell, dev, "ssm_update",
                              "jit_step") is None
    # another family's cell has no state to count
    other = {"model": real_cell("sc2-3b-serve-code")["model"]}
    fresh = {"spans": spans, "profiler": Traced()}
    assert ssm.state_roofline(fresh, other, dev, "ssm_update",
                              "jit_step") is None


def test_counts_at_the_published_widths_by_hand():
    """31.46 M in attention, 47.35 M + 20.97 M in the mixer's two
    projections, 330.30 M in the feed-forward, 430.1 M a layer, 2.674 B of
    vocabulary, 5.255 B parameters in 6 layers; the recurrence's constant;
    the state in ``decode_weight_bytes``."""
    from benchmark.harness import weights
    from benchmark.reducers import counts

    m = real_cell()["model"]
    fam = m["family"]
    attn = 5120 * (2560 + 512 + 512) + 2560 * 5120
    mix_in, mix_out = 5120 * (2 * 4096 + 2 * 2 * 256 + 32), 4096 * 5120
    ffn = 3 * 5120 * 21504
    assert (attn, mix_in, mix_out, ffn) == (31_457_280, 47_349_760,
                                            20_971_520, 330_301_440)
    assert fam.segments(m) == (4096, 4096, 512, 512, 32)
    assert fam._size(m, fam.ATTENTION) == attn
    assert fam._size(m, fam.MIXER_PROJ) == mix_in + mix_out
    assert fam._size(m, fam.FFN) == ffn
    rest = 4 * 5120 + 5120 + 3 * 32 + 4096      # taps, bias, three a head, norm
    layer = attn + mix_in + mix_out + rest + ffn + 2 * 5120
    assert round(layer / 1e6, 1) == 430.1
    total = 6 * layer + 2 * 261120 * 5120 + 5120
    assert weights.n_params(m) == total and round(total / 1e9, 3) == 5.255
    assert counts.matmul_params(m) == (
        6 * (attn + mix_in + mix_out + ffn) + 5120 * 261120)
    # the recurrence at the published tile: C B^T, its product with xs, the
    # tile's state and the read of the entering one
    rec = 2 * 128 * 256 * 2 + 32 * (2 * 128 * 128 + 4 * 128 * 256)
    assert rec == 5_373_952 and fam.recurrence_flops(m) == rec
    per_key = 2 * 20 * 256
    assert fam.attention_flops(m, 100) == 6 * (per_key * 100 + rec)
    both = fam.attention_flops(m, np.array([1, 3072]))
    assert both.tolist() == [6 * (per_key + rec), 6 * (per_key * 3072 + rec)]
    ctx = np.arange(1, 301)
    assert counts.request_flops(m, 256, 44) == pytest.approx(
        2.0 * counts.matmul_params(m) * 300
        + 6 * (per_key * ctx.sum() + 300 * rec))
    # the cache: 4 KV heads of 128, K and V, bf16, 6 layers
    assert fam.kv_row_bytes(m) == 2048
    assert counts.kv_bytes_per_token(m) == 12288
    # the state: 32 x 128 x 256 float32 a layer a stream, 25.2 MB a stream
    assert fam.state_bytes(m) == 4_194_304
    fixed = (total - 261120 * 5120) * 2
    assert round(fixed / 1e9, 2) == 7.84
    assert counts.weight_bytes(m) == fixed
    obs = {"decode_stream_ticks": 6000, "decode_ticks": 100}
    assert counts.weight_bytes(m, obs) == fixed + 60 * 2 * 6 * 4_194_304
    assert counts.weight_bytes(m, {"decode_ticks": 0}) == fixed


def test_the_real_configuration_keeps_every_published_width():
    """Every key of the catalog's row is at the top level of the file under
    its own name and value, but the depth; the published values are kept
    whole beside them; the cells' files say what ISSUE 35 says."""
    config = json.loads((BENCH / "configs" / "falcon-h1-34b-pp12.json")
                        .read_text())
    pub = config["published"]
    assert config["reduced"] == ["num_hidden_layers"]
    for key, value in pub.items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], pub["num_hidden_layers"]) == (6, 72)
    assert (pub["hidden_size"], pub["num_attention_heads"],
            pub["num_key_value_heads"], pub["head_dim"],
            pub["intermediate_size"], pub["vocab_size"], pub["mamba_d_ssm"],
            pub["mamba_n_heads"], pub["mamba_d_head"], pub["mamba_d_state"],
            pub["mamba_n_groups"], pub["mamba_d_conv"]) == (
        5120, 20, 4, 128, 21504, 261120, 4096, 32, 128, 256, 2, 4)
    assert set(config["reduced_from"]) == set(config["reduced"])
    assert {"state_precision", "multipliers", "gated_norm", "weights"} \
        <= set(config["assumed"])
    assert any("mamba_chunk_size" in d for d in config["departures"])
    assert "12 pipeline stages of 6 layers" in config["deployment"]
    cell = real_cell()
    m, job = cell["model"], cell["job"]
    assert (m["n_layers"], m["vocab_size"], m["key_multiplier"],
            m["ssm_multipliers"][3]) == (6, 261120, 0.011048543456039804,
                                         0.5)
    assert job["serve_config"] == {"slots": 64, "block_size": 16,
                                   "num_blocks": 8193, "max_len": 3072,
                                   "prefill_chunk": 512}
    assert (job["clients"], job["shape_seed"], job["shape_pool"],
            job["check_requests"]) == (64, 20261004, 512, 6)
    assert job["prompt_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.9, "min": 32, "max": 2048}
    assert job["output_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.6, "min": 32, "max": 1024}
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    listed = {x["name"] for x in bench["per_layer"]
              if CELL in x.get("workloads", ())}
    assert listed == set(cell["per_layer"]) and len(listed) == 17
    # the second cell: the server of closed16-code, answers of 512 to 1024
    agent = real_cell("sc2-3b-serve-agent")
    code = real_cell("sc2-3b-serve-code")
    assert agent["job"]["serve_config"] == code["job"]["serve_config"]
    assert agent["job"]["output_len"] == {
        "dist": "lognormal", "median": 768, "sigma": 0.25, "min": 512,
        "max": 1024}
    assert agent["job"]["prompt_len"] == {
        "dist": "lognormal", "median": 128, "sigma": 0.4, "min": 64,
        "max": 256}
    assert (agent["job"]["clients"], agent["job"]["shape_seed"]) == (
        16, 20261005)
    assert agent["per_layer"] == [x for x in code["per_layer"]
                                  if x != "decode_gather_ms.serve"]
    assert agent["limits"] == code["limits"]


def test_a_program_without_the_mixer_fails_at_once_by_name(monkeypatch):
    """The parent of the PR that brought the mixer cannot build the
    configuration: the adapter says so and stops before any weight is
    made."""
    from neural_networks_parallel_training_with_mpi_tpu import models

    fam = real_cell()["model"]["family"]

    def older(**kw):
        raise TypeError("TransformerConfig.__init__() got an unexpected "
                        "keyword argument 'ssm_heads'")

    monkeypatch.setattr(models, "TransformerConfig", older)
    with pytest.raises(SystemExit, match="family ssm_attn_parallel.*ssm_heads"):
        fam.transformer_config(real_cell()["model"])
