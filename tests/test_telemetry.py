"""Telemetry subsystem (train.telemetry, DESIGN.md §7): on-device step
metrics, flight recorder, MFU accounting, run-health heartbeat.

The load-bearing properties:

* metrics are PURE OBSERVATION — params are bitwise-identical with
  telemetry on vs off (including under the skip guard, whose norm
  reduction the metrics path shares via ``Optimizer.update_with_norm``);
* the flight recorder dumps a postmortem on every abnormal event
  (rollback with straddling records, SIGTERM, crash), so a relaunch can
  read what the run was doing when it died;
* the analytic FLOPs the MFU divides by match a hand count;
* the heartbeat is fresh while the run lives and the supervisor kills a
  child whose heartbeat goes stale.
"""

import dataclasses
import json
import math
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from neural_networks_parallel_training_with_mpi_tpu.config import (
    DataConfig, MeshConfig, ModelConfig, TrainConfig, build_argparser,
    config_from_args,
)
from neural_networks_parallel_training_with_mpi_tpu.train import (
    telemetry as telemetry_lib,
)
from neural_networks_parallel_training_with_mpi_tpu.train.trainer import (
    Trainer,
)

REPO = pathlib.Path(__file__).resolve().parent.parent


def _cfg(**kw):
    base = dict(nepochs=2, full_batch=False, batch_size=8, lr=1e-3,
                momentum=0.0, data=DataConfig(n_samples=32),
                mesh=MeshConfig(data=8), metrics_every=1)
    base.update(kw)
    return TrainConfig(**base)


def _records(telemetry_dir):
    with open(os.path.join(telemetry_dir, "metrics.jsonl")) as f:
        return [json.loads(line) for line in f if line.strip()]


# ------------------------------------------------------- metrics + heartbeat


def test_metrics_stream_heartbeat_and_summary(tmp_path, mesh8, capsys):
    """Acceptance core: a run with --telemetry_dir emits per-step metrics
    JSONL containing grad_norm/param_norm/update_ratio/loss/mfu, plus a
    fresh final heartbeat — and tools/metrics_summary.py renders it."""
    d = str(tmp_path / "telem")
    t = Trainer(_cfg(telemetry_dir=d), mesh=mesh8)
    result = t.fit()
    recs = _records(d)
    assert len(recs) == result["steps"] == 8
    step_recs = [r for r in recs if r.get("kind") == "step"]
    for key in telemetry_lib.METRIC_KEYS:       # loss, grad_norm, ...
        assert all(key in r for r in step_recs), key
    assert all(math.isfinite(r["grad_norm"]) and r["grad_norm"] > 0
               for r in step_recs)
    assert all(r["param_norm"] > 0 for r in step_recs)
    assert all(0 <= r["update_ratio"] for r in step_recs)
    # mfu + step_time appear once dispatch-to-dispatch time exists; off
    # the TPU the field is present and null (no chip peak to divide by)
    timed = [r for r in step_recs if "step_time_ms" in r]
    assert timed and all("mfu" in r and r["mfu"] is None for r in timed)
    assert "mfu" in result and result["mfu"] is None
    assert result["model_flops_per_sec"] > 0
    hb = telemetry_lib.read_heartbeat(os.path.join(d, "heartbeat.json"))
    assert hb["step"] == 8 and hb["final"] is True
    assert telemetry_lib.heartbeat_age_s(
        os.path.join(d, "heartbeat.json")) < 60
    # no abnormal event -> no postmortem
    assert not os.path.exists(os.path.join(d, "postmortem.json"))
    # the summary CLI renders percentiles from the same artifacts
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import metrics_summary
    finally:
        sys.path.pop(0)
    assert metrics_summary.main([d]) == 0
    out = capsys.readouterr().out
    assert "grad_norm" in out and "heartbeat: step 8" in out


def test_params_bitwise_identical_telemetry_on_off(tmp_path, mesh8):
    """Acceptance: metrics are pure observation.  With the skip guard ON,
    the metrics path hands its norm to the guard (update_with_norm) — the
    trajectory must still be bitwise-equal to the telemetry-off run."""
    def fit_params(telem, guard):
        cfg = _cfg(lr=1e-2, momentum=0.9, skip_nonfinite=guard,
                   telemetry_dir=str(tmp_path / f"t{telem}{guard}")
                   if telem else None)
        t = Trainer(cfg, mesh=mesh8)
        t.fit()
        return jax.device_get(t.state.params)

    for guard in (False, True):
        a, b = fit_params(False, guard), fit_params(True, guard)
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_metrics_on_gspmd_layout(tmp_path, mesh8):
    """The GSPMD (fsdp) path carries the same metrics vector, computed in
    global view."""
    from neural_networks_parallel_training_with_mpi_tpu.parallel.mesh import (
        make_mesh,
    )

    d = str(tmp_path / "telem")
    mesh = make_mesh(MeshConfig(data=4, fsdp=2), devices=mesh8.devices.ravel())
    t = Trainer(_cfg(mesh=MeshConfig(data=4, fsdp=2), telemetry_dir=d),
                mesh=mesh)
    assert t.gspmd and t.telemetry_metrics
    t.fit()
    recs = [r for r in _records(d) if r.get("kind") == "step"]
    assert recs and all(k in recs[-1] for k in telemetry_lib.METRIC_KEYS)


def test_metrics_with_multi_step_dispatch(tmp_path, mesh8):
    """steps_per_dispatch=3: one record per dispatch boundary crossing,
    carrying the dispatch's LAST step's metrics."""
    d = str(tmp_path / "telem")
    t = Trainer(_cfg(steps_per_dispatch=3, telemetry_dir=d), mesh=mesh8)
    result = t.fit()
    recs = [r for r in _records(d) if r.get("kind") == "step"]
    # 4 steps/epoch at k=3 -> dispatches end at steps 3, 4, 7, 8
    assert [r["step"] for r in recs] == [3, 4, 7, 8]
    assert result["steps"] == 8
    assert all("grad_norm" in r for r in recs)
    # skip visibility: a nan fault poisons the WHOLE first k=3 dispatch
    # (fault granularity is the dispatch); all 3 skip fires must reach
    # the stream even though only the dispatch's LAST step's other
    # metrics are reported (the skip count sums over the scan axis)
    d2 = str(tmp_path / "telem2")
    t2 = Trainer(_cfg(steps_per_dispatch=3, skip_nonfinite=True,
                      faults="nan@0?max=1", telemetry_dir=d2), mesh=mesh8)
    t2.fit()
    recs2 = [r for r in _records(d2) if r.get("kind") == "step"]
    assert recs2[0]["step"] == 3 and recs2[0]["skipped"] == 3.0
    assert t2.telemetry.skipped_total == 3


def test_sparse_metrics_cannot_lose_skip_fires(tmp_path, mesh8):
    """metrics_every=4 with a nan at step 2 (never a sampled boundary):
    the cumulative counter carried by the step-4 record still surfaces
    the fire as a differenced skip event."""
    d = str(tmp_path / "telem")
    t = Trainer(_cfg(metrics_every=4, skip_nonfinite=True,
                     faults="nan@1?max=1", telemetry_dir=d), mesh=mesh8)
    t.fit()
    steps = [r for r in _records(d) if r.get("kind") == "step"]
    assert [r["step"] for r in steps] == [4, 8]
    assert steps[0]["skipped"] == 1.0          # cumulative at step 4
    assert t.telemetry.skipped_total == 1
    # the differenced fire reached the flight recorder as a skip event
    assert any(r.get("event") == "skip" and r.get("fires") == 1
               for r in t.telemetry.recorder.records)


def test_zero1_rides_the_full_metrics_stream(tmp_path, mesh8):
    """zero1 used to fall back to the loss-only stream (its update
    consumes a scattered gradient shard); since the update-sharding
    layer it computes the GLOBAL grad norm from the shards via one
    extra psum, so the full metrics vector rides along — and params
    stay bitwise-identical with metrics on vs off
    (tests/test_update_sharding.py pins that half)."""
    d = str(tmp_path / "telem")
    t = Trainer(_cfg(update_sharding="zero1", optimizer="adam",
                     telemetry_dir=d), mesh=mesh8)
    assert t.telemetry_metrics and t.telemetry.enabled
    t.fit()
    recs = [r for r in _records(d) if r.get("kind") == "step"]
    assert recs and all("loss" in r and "grad_norm" in r
                        and "update_ratio" in r for r in recs)


def test_heartbeat_only_mode_final_step(tmp_path, mesh8):
    """metrics_every=0: no metrics stream, but the heartbeat still tracks
    the run and the FINAL beat carries the real step (not 0 — no record
    ever carried one to fall back on)."""
    d = str(tmp_path / "telem")
    t = Trainer(_cfg(metrics_every=0, telemetry_dir=d), mesh=mesh8)
    result = t.fit()
    assert not t.telemetry_metrics  # no on-device metrics wired
    assert not os.path.exists(os.path.join(d, "metrics.jsonl")) or \
        _records(d) == []
    hb = telemetry_lib.read_heartbeat(os.path.join(d, "heartbeat.json"))
    assert hb["step"] == result["steps"] == 8 and hb["final"] is True


def test_cli_flags_plumbed():
    args = build_argparser().parse_args(
        ["--telemetry_dir", "/tmp/x", "--metrics_every", "5",
         "--flight_recorder", "32"])
    cfg = config_from_args(args)
    assert (cfg.telemetry_dir, cfg.metrics_every, cfg.flight_recorder) == \
        ("/tmp/x", 5, 32)
    dflt = TrainConfig()
    assert dflt.telemetry_dir is None and dflt.metrics_every == 1


# ------------------------------------------------------------ flight recorder


def test_postmortem_on_rollback_straddles(tmp_path, mesh8):
    """Acceptance: under an injected step-N nan fault the postmortem's
    last records STRADDLE the rollback — pre-rollback step records and
    skip events, the rollback event, and >= 1 post-rollback record."""
    d = str(tmp_path / "telem")
    cfg = _cfg(nepochs=6, skip_nonfinite=True, rollback_after=2,
               max_rollbacks=2, checkpoint_dir=str(tmp_path / "ck"),
               checkpoint_every=4, faults="nan@10-12?max=3",
               telemetry_dir=d)
    result = Trainer(cfg, mesh=mesh8).fit()
    assert result["rollbacks"] == 1
    pm = json.load(open(os.path.join(d, "postmortem.json")))
    assert pm["reason"] == "rollback"
    kinds = [(r.get("kind"), r.get("event")) for r in pm["records"]]
    ri = [i for i, r in enumerate(pm["records"])
          if r.get("event") == "rollback"]
    assert ri, kinds
    assert any(r.get("kind") == "step" for r in pm["records"][:ri[0]])
    assert any(r.get("kind") == "step" for r in pm["records"][ri[0] + 1:])
    assert any(r.get("event") == "skip" for r in pm["records"])


def test_postmortem_on_sigterm(tmp_path, mesh8):
    d = str(tmp_path / "telem")
    cfg = _cfg(nepochs=10, checkpoint_dir=str(tmp_path / "ck"),
               faults="sigterm@7", telemetry_dir=d)
    result = Trainer(cfg, mesh=mesh8).fit()
    assert result.get("preempted") is True
    pm = json.load(open(os.path.join(d, "postmortem.json")))
    assert pm["reason"].startswith("sigterm")
    assert any(r.get("event") == "sigterm" for r in pm["records"])


def test_postmortem_on_crash_exception(tmp_path, mesh8):
    """An unhandled exception escaping the step loop dumps a crash
    postmortem from fit's finally (the in-process 'segfault stand-in';
    the os._exit fault is covered by the supervised CLI test below)."""
    d = str(tmp_path / "telem")
    t = Trainer(_cfg(nepochs=4, telemetry_dir=d), mesh=mesh8)
    real_step, calls = t.train_step, []

    def exploding(state, batch):
        calls.append(1)
        if len(calls) == 6:
            raise RuntimeError("synthetic device loss")
        return real_step(state, batch)

    t.train_step = exploding
    with pytest.raises(RuntimeError, match="synthetic"):
        t.fit()
    pm = json.load(open(os.path.join(d, "postmortem.json")))
    assert pm["reason"].startswith("crash: RuntimeError")
    assert any(r.get("kind") == "step" for r in pm["records"])


def test_postmortem_on_anomaly_abort(tmp_path, mesh8):
    from neural_networks_parallel_training_with_mpi_tpu.train.resilience import (
        AnomalyAbort,
    )

    d = str(tmp_path / "telem")
    cfg = _cfg(nepochs=8, skip_nonfinite=True, rollback_after=2,
               max_rollbacks=0, checkpoint_dir=str(tmp_path / "ck"),
               checkpoint_every=2, faults="nan@7-999", telemetry_dir=d)
    with pytest.raises(AnomalyAbort):
        Trainer(cfg, mesh=mesh8).fit()
    pm = json.load(open(os.path.join(d, "postmortem.json")))
    assert pm["reason"] == "anomaly_abort"


def test_flight_recorder_ring_is_bounded(tmp_path, mesh8):
    d = str(tmp_path / "telem")
    cfg = _cfg(nepochs=4, flight_recorder=5, faults="sigterm@14",
               telemetry_dir=d)
    Trainer(cfg, mesh=mesh8).fit()
    pm = json.load(open(os.path.join(d, "postmortem.json")))
    assert pm["n_records"] <= 5  # the ring dropped older records


# ------------------------------------------------------------ MFU accounting


def test_train_step_flops_hand_counted_transformer():
    """The analytic FLOPs the MFU divides by, against a literal hand
    count: B=3, T=4, d=8, H=2, ff=16, V=13, 1 layer, gelu."""
    from neural_networks_parallel_training_with_mpi_tpu.models.transformer import (
        Transformer, TransformerConfig,
    )

    B, T, d, ff, V = 3, 4, 8, 16, 13
    m = Transformer(TransformerConfig(vocab_size=V, max_seq_len=T,
                                      n_layers=1, d_model=d, n_heads=2,
                                      d_ff=ff))
    qkv = 2 * B * T * d * (3 * d)          # fused qkv projection
    attn_out = 2 * B * T * d * d           # output projection
    scores_values = 2 * (2 * B * T * T * d)  # QK^T and attn @ V
    ffn = 2 * (2 * B * T * d * ff)         # ff_in + ff_out
    head = 2 * B * T * d * V               # LM head (the CE logits)
    fwd = qkv + attn_out + scores_values + ffn + head
    assert m.fwd_flops((B, T)) == fwd
    assert telemetry_lib.train_step_flops(m, (B, T)) == 3.0 * fwd


def test_train_step_flops_gqa_swiglu_moe_variants():
    from neural_networks_parallel_training_with_mpi_tpu.models.transformer import (
        Transformer, TransformerConfig,
    )

    B, T, d, ff, V = 2, 4, 8, 16, 13
    base = dict(vocab_size=V, max_seq_len=T, n_layers=1, d_model=d,
                n_heads=2, d_ff=ff)
    # GQA: 1 of 2 KV heads -> qkv width d + 2 * 1 * (d/2) = 2d (vs 3d)
    gqa = Transformer(TransformerConfig(n_kv_heads=1, **base))
    full = Transformer(TransformerConfig(**base))
    assert full.fwd_flops((B, T)) - gqa.fwd_flops((B, T)) == \
        2 * B * T * d * d
    # SwiGLU adds the third (d, ff) gate matmul
    swi = Transformer(TransformerConfig(activation="swiglu", **base))
    assert swi.fwd_flops((B, T)) - full.fwd_flops((B, T)) == \
        2 * B * T * d * ff
    # MoE top-2 over 4 experts: 2x the FFN matmuls + the router
    moe = Transformer(TransformerConfig(moe_experts=4, moe_top_k=2, **base))
    ffn = 2 * (2 * B * T * d * ff)
    router = 2 * B * T * d * 4
    assert moe.fwd_flops((B, T)) - full.fwd_flops((B, T)) == ffn + router


def test_train_step_flops_mlp_and_peak_table():
    from neural_networks_parallel_training_with_mpi_tpu.models.mlp import MLP

    m = MLP(in_features=2, hidden=(3,), out_features=1)
    assert m.fwd_flops((5, 2)) == 2 * 5 * (2 * 3 + 3 * 1)
    assert telemetry_lib.train_step_flops(m, (5, 2)) == 3.0 * 2 * 5 * 9
    assert telemetry_lib.peak_flops_per_chip("TPU v5e") == 197e12
    assert telemetry_lib.peak_flops_per_chip("TPU v4") == 275e12
    assert telemetry_lib.peak_flops_per_chip("cpu") is None
    # the benchmark keeps its own table: the two agree until ROADMAP D2
    # merges them
    peaks = json.loads(
        (REPO / "benchmark" / "reducers" / "peaks.json").read_text())
    assert (telemetry_lib.peak_flops_per_chip("TPU v5 lite")
            == peaks["devices"]["TPU v5 lite"]["bf16_flops"])
    # no utilization against an invented peak: off-TPU there is none
    assert telemetry_lib.telemetry_peak_flops("cpu", "cpu") is None
    assert telemetry_lib.telemetry_peak_flops("TPU v4", "tpu") == 275e12


# ----------------------------------------------------- heartbeat + supervisor


def test_supervise_kills_stale_heartbeat_child(tmp_path):
    """External hang detection: a child that beats once (arming the
    monitor) and then stalls is killed and reported as EXIT_HANG (retry
    class).  A PRE-EXISTING heartbeat from a previous run must NOT arm
    the monitor — the compile-exempt arming discipline."""
    from neural_networks_parallel_training_with_mpi_tpu.train.resilience import (
        EXIT_HANG, supervise,
    )

    hb = tmp_path / "heartbeat.json"
    hb.write_text("{}")  # stale leftover: does not arm on its own
    child = ("import pathlib, time\n"
             "time.sleep(0.3)\n"  # 'compile': no beat yet, no kill
             f"pathlib.Path({str(hb)!r}).write_text('{{}}')\n"
             "time.sleep(60)\n")
    logs = []
    rc = supervise([sys.executable, "-c", child],
                   max_restarts=0, backoff=0.0, log=logs.append,
                   heartbeat_path=str(hb), heartbeat_timeout=1.0,
                   _sleep=lambda s: None)
    assert rc == EXIT_HANG
    assert any("heartbeat stale" in m for m in logs)


def test_supervise_fresh_heartbeat_child_completes(tmp_path):
    """A healthy child refreshing its heartbeat is NOT killed."""
    from neural_networks_parallel_training_with_mpi_tpu.train.resilience import (
        supervise,
    )

    hb = tmp_path / "heartbeat.json"
    child = ("import time, pathlib\n"
             f"p = pathlib.Path({str(hb)!r})\n"
             "for _ in range(8):\n"
             "    p.write_text('{}')\n"
             "    time.sleep(0.25)\n")
    rc = supervise([sys.executable, "-c", child], max_restarts=0,
                   backoff=0.0, heartbeat_path=str(hb),
                   heartbeat_timeout=1.5, _sleep=lambda s: None)
    assert rc == 0


def test_supervise_compile_phase_exempt_from_heartbeat_kill(tmp_path):
    """A child whose FIRST heartbeat write takes longer than the timeout
    (first-step compile) must not be killed: the monitor arms only at
    the first write, like the in-process watchdog's first pat()."""
    from neural_networks_parallel_training_with_mpi_tpu.train.resilience import (
        supervise,
    )

    hb = tmp_path / "heartbeat.json"
    child = ("import time, pathlib\n"
             "time.sleep(2.5)\n"  # 'compile' > heartbeat_timeout
             f"pathlib.Path({str(hb)!r}).write_text('{{}}')\n")
    rc = supervise([sys.executable, "-c", child], max_restarts=0,
                   backoff=0.0, heartbeat_path=str(hb),
                   heartbeat_timeout=1.0, _sleep=lambda s: None)
    assert rc == 0


def _clean_env():
    from neural_networks_parallel_training_with_mpi_tpu.utils import (
        faults as faults_lib,
        platform as plat,
    )

    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop(faults_lib.ENV_VAR, None)
    plat.force_host_device_count(None, env=env)
    return env


def test_crash_fault_dump_and_supervisor_pointer(tmp_path):
    """Acceptance e2e: an injected os._exit crash leaves a postmortem
    (utils.faults' emergency hook), the supervisor's relaunch log points
    at it, the relaunch resumes and completes, and the heartbeat is fresh
    under the supervisor with the final step."""
    d = tmp_path / "telem"
    out = subprocess.run(
        [sys.executable, "-m", "neural_networks_parallel_training_with_mpi_tpu",
         "--platform", "cpu", "--num_devices", "2", "--dataset", "regression",
         "--n_samples", "32", "--batch_size", "8", "--no-full-batch",
         "--nepochs", "4", "--checkpoint_dir", str(tmp_path / "ck"),
         "--checkpoint_every", "3", "--telemetry_dir", str(d),
         "--faults", f"crash@9?once={tmp_path / 'crashed'}",
         "--supervise", "2", "--supervise_backoff", "0.1"],
        capture_output=True, text=True, timeout=240, env=_clean_env(),
        cwd=str(REPO))
    text = out.stdout + out.stderr
    assert out.returncode == 0, text[-3000:]
    assert "injected crash at step 9" in text
    assert "child left a postmortem" in text
    pm = json.load(open(d / "postmortem.json"))
    assert pm["reason"].startswith("crash@9")
    hb = telemetry_lib.read_heartbeat(str(d / "heartbeat.json"))
    assert hb is not None and hb["step"] == 16 and hb.get("final") is True


# ------------------------------------------------------------------ overhead


@pytest.mark.slow
def test_telemetry_happy_path_overhead(tmp_path, mesh8):
    """Telemetry adds the metrics-vector norms inside the step plus the
    lag-2 fetch per dispatch.  At the CPU bench's transformer scale the
    measured overhead is ~0.6% (see DESIGN.md §7); this micro-model run
    asserts loosely (the fixed norm passes are proportionally larger
    here) and prints the measured number as the record."""
    import time

    def steptime(telem):
        cfg = _cfg(nepochs=1, batch_size=32,
                   telemetry_dir=str(tmp_path / "t") if telem else None,
                   data=DataConfig(dataset="lm", n_samples=64, seq_len=64,
                                   vocab_size=64),
                   model=ModelConfig(arch="transformer", n_layers=2,
                                     d_model=64, n_heads=4, d_ff=128,
                                     vocab_size=64, max_seq_len=64,
                                     attention="dense"),
                   loss="cross_entropy")
        t = Trainer(cfg, mesh=mesh8)
        t.init_state()
        batch = next(iter(t.loader.epoch(0)))
        state = t.state
        state, out = t.train_step(state, batch)  # compile
        jax.block_until_ready(out)
        n = 20
        t0 = time.perf_counter()
        for _ in range(n):
            state, out = t.train_step(state, batch)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n

    # INTERLEAVED min-of-k pairs: the test host is a single shared core,
    # and grouping all base runs before all telemetry runs lets one load
    # spike masquerade as overhead (observed a 1.3x phantom that way)
    base = telem = None
    for _ in range(3):
        b, t_ = steptime(False), steptime(True)
        base = b if base is None else min(base, b)
        telem = t_ if telem is None else min(telem, t_)
    ratio = telem / base
    print(f"\ntelemetry overhead: {base * 1e3:.2f}ms -> "
          f"{telem * 1e3:.2f}ms ({(ratio - 1) * 100:+.1f}%)")
    assert ratio < 1.4, f"telemetry overhead {ratio:.2f}x"
