"""Goodput accounting & step anatomy (utils/goodput.py, utils/jsonl.py,
tools/goodput_report.py).

Pins, by acceptance criterion:

* **sum invariant**: the offline ledger classifies 100% of every
  process's covered wall-clock — categories sum to the interval on
  overlapping spans, gaps, crashes, decommissions; residual ~0.
* **crash pricing**: a supervised crash->relaunch comes back as
  ``relaunch_gap`` (the supervisor's backoff window) plus ``rollback``
  (the re-trained step window after restore) — never dropped time.
* **torn-line tolerance**: the shared JSONL reader skips-and-counts a
  torn final line (a crashed writer's last record) instead of dying.
* **tool smoke**: goodput_report runs under ``python -S`` (stdlib
  proof).

The subprocess supervised-crash e2e is marked chaos; everything else is
core-lane cheap (no jax imports).  ``-m goodput`` runs the lane alone.
"""

import json
import os
import pathlib
import subprocess
import sys
import time

import pytest

from neural_networks_parallel_training_with_mpi_tpu.train import (
    resilience as res,
    trace as trace_lib,
)
from neural_networks_parallel_training_with_mpi_tpu.utils import (
    goodput as gp,
    jsonl as jz,
)

pytestmark = pytest.mark.goodput

REPO = pathlib.Path(__file__).resolve().parent.parent
PKG = REPO / "neural_networks_parallel_training_with_mpi_tpu"


def _load_tool(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        f"_gp_test_{name}", REPO / "tools" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _span(name, t, dur, run="r", p=0, inc=0, **attrs):
    return {"kind": "span", "name": name, "t": t, "dur": dur,
            "run": run, "p": p, "inc": inc, **attrs}


def _sum_ok(proc):
    cats = proc["categories"]
    assert proc["sum_ok"], proc
    assert abs(sum(cats.values()) - proc["covered_s"]) < 2e-5, proc
    return cats


# ---------------------------------------------------------------------------
# offline ledger: the sum-to-covered invariant
# ---------------------------------------------------------------------------

def test_ledger_sums_overlaps_and_gaps():
    # dispatch 0-1, async ckpt fully shadowed 0.2-0.8, gap 1-1.5 between
    # dispatches (pipeline both sides -> step), dispatch 1.5-2, lone
    # unknown span 2.5-2.6 (idle catch-all) with an unbracketed gap
    recs = [
        _span("dispatch", 0.0, 1.0, step=0),
        _span("ckpt", 0.2, 0.6),
        _span("dispatch", 1.5, 0.5, step=1),
        _span("weird_custom_phase", 2.5, 0.1),
    ]
    led = gp.build_ledger(recs)
    (proc,) = led["processes"]
    cats = _sum_ok(proc)
    assert proc["covered_s"] == pytest.approx(2.6)
    # shadowed ckpt owns nothing (step outranks ckpt in PRIORITY)
    assert cats["ckpt"] == pytest.approx(0.0)
    assert cats["step"] == pytest.approx(2.0)   # 1.0 + 0.5s gap + 0.5
    assert cats["idle"] == pytest.approx(0.6)   # 0.5 unbracketed + 0.1
    assert led["fleet"]["sum_ok"]


def test_ledger_prices_relaunch_gap_and_retrain():
    # inc 0: steps 0..2, crash; inc 1 starts 3s later and REPLAYS
    # steps 0..2 before new ground at 3..4
    recs = [_span("dispatch", float(i), 1.0, inc=0, step=i)
            for i in range(3)]
    recs += [_span("dispatch", 6.0 + i, 1.0, inc=1, step=i)
             for i in range(5)]
    sup = [
        {"kind": "supervisor", "event": "exit", "t": 3.1, "run": "r",
         "inc": 0, "rc": 1},
        {"kind": "supervisor", "event": "relaunch", "t": 5.9, "run": "r",
         "inc": 1},
    ]
    led = gp.build_ledger(recs, sup)
    (proc,) = led["processes"]
    cats = _sum_ok(proc)
    # supervisor gap: last inc-0 span end (3.0) -> first inc-1 span (6.0)
    assert cats["relaunch_gap"] == pytest.approx(3.0)
    # replayed steps 0..2 of inc 1 are repaid work
    assert cats["rollback"] == pytest.approx(3.0)
    assert cats["step"] == pytest.approx(3.0 + 2.0)  # inc0 fresh + 3..4
    assert led["fleet"]["relaunches"] == 1
    assert len(proc["incarnations"]) == 2


def test_ledger_extends_decommission_exit_as_drain():
    recs = [_span("dispatch", 0.0, 1.0, step=0)]
    sup = [{"kind": "supervisor", "event": "exit", "t": 1.5, "run": "r",
            "inc": 0, "rc": gp.EXIT_DECOMMISSION}]
    led = gp.build_ledger(recs, sup)
    (proc,) = led["processes"]
    cats = _sum_ok(proc)
    assert cats["drain"] == pytest.approx(0.5)
    assert proc["covered_s"] == pytest.approx(1.5)


def test_ledger_separates_processes_and_counts_decisions():
    recs = [_span("dispatch", 0.0, 1.0, p=0, step=0),
            _span("dispatch", 0.0, 2.0, p=1, step=0)]
    led = gp.build_ledger(recs, (), [{"action": "scale_up"}] * 3)
    assert led["fleet"]["n_processes"] == 2
    assert led["fleet"]["decisions"] == 3
    assert led["fleet"]["covered_s"] == pytest.approx(3.0)


# ---------------------------------------------------------------------------
# online meter: frontier rule + exact snapshot sum
# ---------------------------------------------------------------------------

def test_meter_frontier_and_snapshot_sum():
    clock = {"t": 100.0}
    m = gp.GoodputMeter(now_fn=lambda: clock["t"])
    m.t_start = 0.0
    m._frontier = 0.0
    m.on_span("dispatch", 0.0, 1.0)          # step: 0-1
    m.on_span("ckpt", 0.2, 0.5)              # fully shadowed: adds 0
    m.on_span("dispatch", 1.5, 0.5)          # 0.5 pipeline gap -> step
    m.on_span("eval", 3.0, 1.0)              # 1.0 non-pipe gap -> idle
    clock["t"] = 4.5                         # 0.5 unobserved tail
    snap = m.snapshot()
    cats = snap["categories"]
    # step: 1.0 (span) + 0.5 (pipeline-bracketed gap) + 0.5 (span)
    assert cats["step"] == pytest.approx(2.0)
    assert cats["ckpt"] == pytest.approx(0.0)
    assert cats["eval"] == pytest.approx(1.0)
    assert cats["idle"] == pytest.approx(1.5)
    assert snap["covered_s"] == pytest.approx(4.5)
    assert sum(cats.values()) == pytest.approx(snap["covered_s"],
                                               abs=2e-5)
    assert snap["spans"] == 4
    assert snap["goodput_fraction"] == pytest.approx(2.0 / 4.5, abs=1e-4)


def test_meter_rides_the_trace_listener(tmp_path, monkeypatch):
    monkeypatch.setenv("NNPT_PROCESS_ID", "3")
    monkeypatch.setenv("NNPT_RUN_ID", "meter-run")
    tracer = trace_lib.start_run(str(tmp_path), ledger=False)
    meter = gp.GoodputMeter()
    trace_lib.add_listener(meter.on_span)
    try:
        with trace_lib.span("dispatch", step=0):
            time.sleep(0.01)
    finally:
        trace_lib.remove_listener(meter.on_span)
        trace_lib.stop_run(tracer)
    snap = meter.snapshot()
    assert snap["spans"] == 1
    assert snap["categories"]["step"] > 0.0
    rec = gp.goodput_record(snap, role="train", step=0,
                            ident=trace_lib.run_identity())
    assert rec["kind"] == "goodput" and rec["p"] == 3
    assert rec["run"] == "meter-run"


# ---------------------------------------------------------------------------
# step anatomy: roofline + MFU-gap attribution
# ---------------------------------------------------------------------------

def test_step_anatomy_roofline_attribution():
    # ridge = 1e12/1e11 = 10 flops/byte
    compute = gp.step_anatomy(flops=1e9, bytes_accessed=1e7, step_s=0.01,
                              host_s=0.002, peak_flops=1e12, peak_bw=1e11)
    assert compute["roofline_bound"] == "compute"
    assert compute["mfu"] == pytest.approx(0.1)
    frac = compute["mfu_gap"]
    assert (frac["compute_frac"] + frac["host_frac"] + frac["stall_frac"]
            ) == pytest.approx(1.0, abs=1e-3)
    memory = gp.step_anatomy(flops=1e8, bytes_accessed=1e9, step_s=0.02,
                             host_s=0.0, peak_flops=1e12, peak_bw=1e11)
    assert memory["roofline_bound"] == "memory"
    assert memory["memory_s"] == pytest.approx(0.01)
    assert gp.step_anatomy(None, 1e9, 0.01, 0.0, 1e12, 1e11) is None
    assert gp.step_anatomy(1e9, 1e7, 0.0, 0.0, 1e12, 1e11) is None


def test_peak_bw_env_override(monkeypatch):
    monkeypatch.setenv(gp.BW_ENV_VAR, "2.5e11")
    assert gp.peak_bytes_per_s("v5e", "tpu") == pytest.approx(2.5e11)
    monkeypatch.delenv(gp.BW_ENV_VAR)
    assert gp.peak_bytes_per_s("TPU v5e", "tpu") == pytest.approx(8.19e11)
    assert gp.peak_bytes_per_s("", "cpu") == pytest.approx(
        gp.NOMINAL_CPU_BW)


# ---------------------------------------------------------------------------
# torn-line tolerance: the shared JSONL reader
# ---------------------------------------------------------------------------

def test_torn_final_line_skipped_and_counted(tmp_path):
    path = tmp_path / "trace-p0-i0.jsonl"
    path.write_text(
        json.dumps(_span("dispatch", 0.0, 1.0, step=0)) + "\n"
        + '{"kind": "span", "name": "dispa')  # writer died mid-record
    recs, skipped = jz.read_jsonl(str(path))
    assert len(recs) == 1 and skipped == 1
    led = gp.ledger_from_dir(str(tmp_path))
    assert led["fleet"]["lines_skipped"] == 1
    (proc,) = led["processes"]
    _sum_ok(proc)


def test_reader_missing_file_and_non_dict_lines(tmp_path):
    assert jz.read_jsonl(str(tmp_path / "absent.jsonl")) == ([], 0)
    path = tmp_path / "mixed.jsonl"
    path.write_text('[1, 2]\n{"ok": 1}\nnot json\n')
    recs, skipped = jz.read_jsonl(str(path))
    assert recs == [{"ok": 1}] and skipped == 2


# ---------------------------------------------------------------------------
# tools: python -S report smoke
# ---------------------------------------------------------------------------

def _write_fixture_dir(d):
    with open(d / "trace-p0-i0.jsonl", "w") as f:
        for i in range(3):
            f.write(json.dumps(
                _span("dispatch", float(i), 0.9, step=i)) + "\n")
    with open(d / "supervisor-events.jsonl", "w") as f:
        f.write(json.dumps({"kind": "supervisor", "event": "exit",
                            "t": 3.0, "run": "r", "inc": 0, "rc": 0})
                + "\n")


def test_goodput_report_runs_under_python_S(tmp_path):
    _write_fixture_dir(tmp_path)
    out = subprocess.run(
        [sys.executable, "-S", str(REPO / "tools" / "goodput_report.py"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "fleet" in out.stdout and "goodput" in out.stdout
    js = subprocess.run(
        [sys.executable, "-S", str(REPO / "tools" / "goodput_report.py"),
         str(tmp_path), "--json"],
        capture_output=True, text=True, timeout=60)
    doc = json.loads(js.stdout)
    assert doc["fleet"]["sum_ok"]
    assert all(p["sum_ok"] for p in doc["processes"])


def test_obs_agg_merges_goodput_to_prometheus(tmp_path):
    oa = _load_tool("obs_agg")
    dirs = []
    for i, role in enumerate(("train", "serve")):
        d = tmp_path / f"telem{i}"
        d.mkdir()
        snap = {"covered_s": 10.0,
                "categories": {**gp.zero_categories(), "step": 6.0,
                               "idle": 4.0},
                "goodput_fraction": 0.6, "spans": 5,
                "host_seconds": {}}
        rec = gp.goodput_record(snap, role=role, step=7,
                                ident={"p": i, "run": "r", "inc": 0},
                                t_unix=1000.0)
        (d / "metrics.jsonl").write_text(json.dumps(rec) + "\n")
        dirs.append(str(d))
    doc = oa.aggregate(dirs)
    for role in ("train", "serve"):
        gv = doc["roles"][role]["goodput"]
        assert gv["fraction"] == pytest.approx(0.6)
        assert gv["covered_s"] == pytest.approx(10.0)
    assert doc["fleet"]["goodput_fraction"] == pytest.approx(0.6)
    prom = oa.to_prometheus(doc)
    assert 'nnpt_goodput_seconds_total{role="train",category="step"}' \
        in prom
    assert 'nnpt_goodput_fraction{role="serve"} 0.6' in prom


# ---------------------------------------------------------------------------
# the meter is pure observation: same parameters, same served tokens
# ---------------------------------------------------------------------------

def _goodput_records(telemetry_dir):
    return [r for r in jz.read_jsonl(
        os.path.join(telemetry_dir, "metrics.jsonl"))[0]
        if r.get("kind") == "goodput"]


def test_params_bitwise_identical_goodput_on_off(tmp_path, mesh8):
    """Both runs are traced; the only difference is the span listener,
    its frontier update per span and a snapshot per rollup."""
    import jax
    import numpy as np

    from neural_networks_parallel_training_with_mpi_tpu.config import (
        DataConfig, TrainConfig,
    )
    from neural_networks_parallel_training_with_mpi_tpu.train.trainer import (
        Trainer,
    )

    leaves = {}
    for arm in (False, True):
        tdir = str(tmp_path / f"gp{arm}")
        t = Trainer(TrainConfig(
            nepochs=2, batch_size=8, full_batch=False, lr=1e-2,
            momentum=0.9, skip_nonfinite=True,
            data=DataConfig(dataset="regression", n_samples=32),
            telemetry_dir=tdir, trace=True, metrics_every=1,
            rollup_every=2, goodput=arm), mesh=mesh8)
        t.fit()
        leaves[arm] = jax.tree_util.tree_leaves(
            jax.device_get(t.state.params))
        assert bool(_goodput_records(tdir)) == arm
    for a, b in zip(leaves[False], leaves[True]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_served_tokens_identical_goodput_on_off(tmp_path):
    """The same requests through a ``Scheduler`` with the meter, its
    ``kind="goodput"`` rollups and the burn budget on and off: the
    accounting cannot reach the sampler."""
    from neural_networks_parallel_training_with_mpi_tpu.models.transformer import (  # noqa: E501
        Transformer, TransformerConfig,
    )
    from neural_networks_parallel_training_with_mpi_tpu.serve.scheduler import (  # noqa: E501
        Scheduler, ServeConfig,
    )
    from neural_networks_parallel_training_with_mpi_tpu.utils import prng

    model = Transformer(TransformerConfig(
        vocab_size=64, max_seq_len=64, n_layers=2, d_model=32,
        n_heads=4, d_ff=64))
    params = model.init(prng.init_key(0))
    prompts = [[1, 2, 3], [4, 5, 6, 7], [9, 10]]
    tokens = {}
    for arm in (False, True):
        tdir = str(tmp_path / f"serve{arm}")
        sched = Scheduler(model, params, ServeConfig(
            slots=4, num_blocks=40, block_size=8, prefill_chunk=8,
            telemetry_dir=tdir, rollup_every=4, goodput=arm))
        rids = [sched.submit(p, 8) for p in prompts]
        sched.run_until_drained()
        tokens[arm] = [sched.result(r) for r in rids]
        sched.close()
        assert bool(_goodput_records(tdir)) == arm
    assert tokens[False] == tokens[True]
    assert [len(t) for t in tokens[True]] == [len(p) + 8 for p in prompts]


# ---------------------------------------------------------------------------
# chaos: a REAL supervised crash is priced, end to end
# ---------------------------------------------------------------------------

_CHILD = r'''
import importlib.util, json, os, sys, time


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


trace = _load("_t", sys.argv[1])
trace_dir, marker = sys.argv[2], sys.argv[3]
tracer = trace.start_run(trace_dir, ledger=False)
crash = bool(marker) and not os.path.exists(marker)
for i in range(4):
    with trace.span("dispatch", step=i):
        time.sleep(0.02)
    if crash and i == 1:
        open(marker, "w").close()
        os._exit(1)
tracer.close()
'''


@pytest.mark.chaos
def test_supervised_crash_is_priced_as_gap_plus_retrain(tmp_path):
    script = tmp_path / "child.py"
    script.write_text(_CHILD)
    trace_dir = tmp_path / "trace"
    trace_dir.mkdir()
    marker = str(tmp_path / "crashed.marker")
    spec = res.ChildSpec(
        name="w0", role="train",
        cmd=[sys.executable, "-S", str(script),
             str(PKG / "train" / "trace.py"), str(trace_dir), marker],
        env={"NNPT_PROCESS_ID": "0"}, backoff=0.2)
    sup = res.GroupSupervisor(
        [spec], log=lambda m: None,
        events_path=str(trace_dir / "supervisor-events.jsonl"))
    sup.start()
    deadline = time.time() + 60.0
    while sup.running() and time.time() < deadline:
        sup.poll()
        time.sleep(0.02)
    assert not sup.running(), "supervised chaos run did not drain"
    assert sup.done("w0") == 0
    led = gp.ledger_from_dir(str(trace_dir))
    (proc,) = led["processes"]
    cats = _sum_ok(proc)
    assert len(proc["incarnations"]) == 2
    assert cats["relaunch_gap"] > 0.0      # the supervisor's backoff
    assert cats["rollback"] > 0.0          # replayed steps 0..1
    assert led["fleet"]["sum_ok"]
    assert led["fleet"]["relaunches"] == 1
