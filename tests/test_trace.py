"""Distributed tracing + compile-event ledger (train/trace.py,
utils/compile_ledger.py, tools/trace_report.py).

Pins, by acceptance criterion:

* **bitwise**: params identical trace-on vs trace-off (the ledger's AOT
  path runs the same XLA program the jit path would).
* **recompile attribution**: a deliberate shape (and dtype) change
  produces a ledger entry NAMING the changed signature component.
* **table-churn no-recompile**: the paged-serving invariant asserted
  via the ledger — scheduler churn adds ZERO compile events.
* **merged timeline**: a supervised run that crashed and relaunched
  mid-training merges into one Perfetto trace.json with both
  incarnations (both processes in the slow/chaos 2-process variant),
  correlated by run_id, relaunch gap visible.

Cheap pins run in the budgeted core lane; subprocess crash/relaunch
runs are slow/chaos.  `-m trace` runs this lane alone.
"""

import glob
import json
import os
import pathlib
import socket
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from neural_networks_parallel_training_with_mpi_tpu.config import (
    DataConfig, TrainConfig,
)
from neural_networks_parallel_training_with_mpi_tpu.train import (
    trace as trace_lib,
)
from neural_networks_parallel_training_with_mpi_tpu.utils import (
    compile_ledger as ledger_lib,
)

pytestmark = pytest.mark.trace

REPO = pathlib.Path(__file__).resolve().parent.parent
REPORT = REPO / "tools" / "trace_report.py"


@pytest.fixture(autouse=True)
def _clean_trace_state():
    """Every test starts and ends with no installed tracer/ledger (both
    are process-global) and no inherited identity env."""
    saved = {k: os.environ.pop(k, None)
             for k in (trace_lib.RUN_ID_ENV, trace_lib.INCARNATION_ENV)}
    yield
    trace_lib.stop_run()
    ledger_lib.install(None)
    for k, v in saved.items():
        if v is not None:
            os.environ[k] = v


def _spans(trace_dir, name=None):
    out = []
    for path in glob.glob(os.path.join(trace_dir, "trace-*.jsonl")):
        for line in open(path):
            rec = json.loads(line)
            if rec.get("kind") == "span" and (name is None
                                              or rec["name"] == name):
                out.append(rec)
    return out


def _compiles(trace_dir):
    out = []
    for path in glob.glob(os.path.join(trace_dir, "compiles-*.jsonl")):
        out.extend(json.loads(l) for l in open(path))
    return out


# ---------------------------------------------------------------------------
# span API
# ---------------------------------------------------------------------------

def test_span_records_identity_and_bounds(tmp_path):
    """Every record carries (process_id, run_id, incarnation); the file
    is BOUNDED — past max_events spans drop and the footer counts them."""
    os.environ[trace_lib.RUN_ID_ENV] = "r-abc"
    os.environ[trace_lib.INCARNATION_ENV] = "3"
    tracer = trace_lib.start_run(str(tmp_path), max_events=5)
    assert os.path.basename(tracer.path).endswith("-i3.jsonl")
    for i in range(8):
        with trace_lib.span("dispatch", step=i):
            pass
    trace_lib.stop_run()
    recs = [json.loads(l) for l in open(tracer.path)]
    spans = [r for r in recs if r["kind"] == "span"]
    assert len(spans) == 5  # bounded
    assert all(r["run"] == "r-abc" and r["inc"] == 3 and "p" in r
               for r in spans)
    assert all("t" in r and "dur" in r for r in spans)
    footer = recs[-1]
    assert footer["kind"] == "meta" and footer["dropped"] == 3


def test_span_is_noop_when_uninstalled():
    assert trace_lib.active() is None
    with trace_lib.span("anything", x=1):
        pass  # must not raise, must not allocate a tracer
    assert trace_lib.active() is None


def test_trace_flag_requires_a_directory():
    cfg = TrainConfig(trace=True)  # no telemetry_dir, no trace_dir
    with pytest.raises(ValueError, match="--trace needs"):
        trace_lib.dir_from_config(cfg)
    cfg = TrainConfig(trace=True, telemetry_dir="/tmp/x")
    assert trace_lib.dir_from_config(cfg) == "/tmp/x/trace"
    cfg = TrainConfig(trace_dir="/tmp/y")
    assert trace_lib.dir_from_config(cfg) == "/tmp/y"


def test_cli_flags_plumbed():
    from neural_networks_parallel_training_with_mpi_tpu.config import (
        build_argparser, config_from_args,
    )

    args = build_argparser().parse_args(
        ["--trace_dir", "/tmp/t", "--xla_trace_dir", "/tmp/x"])
    cfg = config_from_args(args)
    assert cfg.trace and cfg.trace_dir == "/tmp/t"
    assert cfg.xla_trace_dir == "/tmp/x"
    cfg2 = config_from_args(build_argparser().parse_args(
        ["--trace", "--telemetry_dir", "/tmp/run"]))
    assert cfg2.trace and cfg2.trace_dir is None


# ---------------------------------------------------------------------------
# compile ledger
# ---------------------------------------------------------------------------

def test_ledger_records_compile_with_cost_and_fingerprint(tmp_path):
    trace_lib.start_run(str(tmp_path))
    fn = ledger_lib.instrument(jax.jit(lambda x: x * 2.0), "double")
    out = fn(jnp.ones((4, 8)))
    assert float(out[0, 0]) == 2.0
    out2 = fn(jnp.ones((4, 8)))  # cache hit: no second event
    assert float(out2[0, 0]) == 2.0
    events = ledger_lib.active().events
    assert len(events) == 1
    e = events[0]
    assert e["name"] == "double" and e["n_compile"] == 1
    assert e["compile_ms"] >= 0 and len(e["hlo_sha256"]) == 64
    assert e["flops"] and e["flops"] > 0
    assert e["signature"] == {"[0]": "float32[4,8]"}
    # the compile itself is a span on the timeline
    trace_lib.stop_run()
    assert _spans(str(tmp_path), "compile:double")


def test_deliberate_shape_change_names_changed_component(tmp_path):
    """Acceptance: a recompile's ledger entry names WHICH part of the
    signature changed — shape first, then dtype."""
    trace_lib.start_run(str(tmp_path))
    fn = ledger_lib.instrument(jax.jit(lambda s, b: (s, b.sum())), "step")
    s = jnp.zeros(())
    fn(s, jnp.ones((4, 8)))
    fn(s, jnp.ones((4, 16)))                 # shape change
    fn(s, jnp.ones((4, 16), jnp.bfloat16))   # dtype change
    ev = ledger_lib.active().events
    assert [e["n_compile"] for e in ev] == [1, 2, 3]
    assert ev[1]["changed"] == {"[1]": {"from": "float32[4,8]",
                                        "to": "float32[4,16]"}}
    assert ev[2]["changed"] == {"[1]": {"from": "float32[4,16]",
                                        "to": "bfloat16[4,16]"}}
    recs = _compiles(str(tmp_path))
    assert len(recs) == 3 and recs[1]["changed"]


def test_ledger_passthrough_without_install():
    calls = []

    class Fake:
        def __call__(self, x):
            calls.append(x)
            return x

    fn = ledger_lib.instrument(Fake(), "fake")
    assert fn(7) == 7 and calls == [7]  # no ledger: raw path, no flatten


def test_ledger_signature_only_for_plain_callables(tmp_path):
    """A wrapper without .lower degrades to a signature-only event
    instead of breaking the run."""
    trace_lib.start_run(str(tmp_path))
    fn = ledger_lib.instrument(lambda x: x + 1, "plain")
    assert fn(np.ones(3))[0] == 2.0
    e = ledger_lib.active().events[0]
    assert "no .lower" in e["note"] and "compile_ms" not in e


# ---------------------------------------------------------------------------
# trainer wiring
# ---------------------------------------------------------------------------

def _cfg(tmp_path, trace=True, **kw):
    base = dict(nepochs=2, batch_size=8, full_batch=False, lr=0.005,
                shuffle=True,
                data=DataConfig(dataset="regression", n_samples=32))
    base.update(kw)
    return TrainConfig(
        telemetry_dir=str(tmp_path / "run") if trace else None,
        trace=trace, **base)


def _digest(params):
    import hashlib

    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(jax.device_get(params)):
        h.update(np.ascontiguousarray(leaf).tobytes())
    return h.hexdigest()


def test_trainer_span_categories_and_ledger(tmp_path, mesh8):
    """fit() emits load/dispatch/fetch/ckpt spans and the step's compile
    lands in the ledger with the layout-tagged name."""
    from neural_networks_parallel_training_with_mpi_tpu.train.trainer import (
        Trainer,
    )

    cfg = _cfg(tmp_path, checkpoint_dir=str(tmp_path / "ck"),
               checkpoint_every=4)
    t = Trainer(cfg, mesh=mesh8)
    res = t.fit()
    assert np.isfinite(res["final_loss"])
    tdir = os.path.join(cfg.telemetry_dir, "trace")
    names = {s["name"] for s in _spans(tdir)}
    assert {"load", "dispatch", "fetch", "ckpt"} <= names
    comps = _compiles(tdir)
    assert any(c["name"] == "train_step[dp]" for c in comps)
    assert all(c["run"] == comps[0]["run"] for c in comps)
    assert trace_lib.active() is None  # fit closed the tracer


def test_params_bitwise_identical_trace_on_off(tmp_path, mesh8):
    """Acceptance: the ledger's AOT execution path and the span writes
    are pure observation — the training trajectory is bitwise-equal to
    the untraced run (guard on, so the skip path is covered too)."""
    from neural_networks_parallel_training_with_mpi_tpu.train.trainer import (
        Trainer,
    )

    t_off = Trainer(_cfg(tmp_path / "off", trace=False,
                         skip_nonfinite=True), mesh=mesh8)
    t_off.fit()
    t_on = Trainer(_cfg(tmp_path / "on", trace=True,
                        skip_nonfinite=True), mesh=mesh8)
    t_on.fit()
    assert _digest(t_off.state.params) == _digest(t_on.state.params)


def test_heartbeat_and_postmortem_carry_device_memory(tmp_path,
                                                      monkeypatch):
    """Satellite: utils/profiling.device_memory_stats snapshots ride the
    heartbeat (compact) and every flight-recorder dump (full) — OOM
    postmortems show per-device memory at death.  CPU reports nothing,
    so the backend is faked."""
    from neural_networks_parallel_training_with_mpi_tpu.train import (
        telemetry as telemetry_lib,
    )
    from neural_networks_parallel_training_with_mpi_tpu.utils import (
        profiling,
    )

    fake = {"TPU_0": {"bytes_in_use": 123, "peak_bytes_in_use": 456,
                      "largest_free_block_bytes": 9}}
    monkeypatch.setattr(profiling, "device_memory_stats", lambda: fake)
    hb = telemetry_lib.Heartbeat(str(tmp_path / "heartbeat.json"))
    hb.beat(7, None, force=True)
    doc = json.load(open(tmp_path / "heartbeat.json"))
    assert doc["device_memory"] == {
        "TPU_0": {"bytes_in_use": 123, "peak_bytes_in_use": 456}}
    rec = telemetry_lib.FlightRecorder(8, str(tmp_path / "pm.json"))
    rec.record({"kind": "step", "step": 1})
    rec.dump("test")
    pm = json.load(open(tmp_path / "pm.json"))
    assert pm["device_memory"]["TPU_0"]["largest_free_block_bytes"] == 9


# ---------------------------------------------------------------------------
# serving wiring: tick spans + the table-churn ledger assertion
# ---------------------------------------------------------------------------

def test_serve_tick_spans_and_churn_adds_no_compiles(tmp_path):
    """Acceptance: the paged-attention table-churn no-recompile
    invariant as a LEDGER assertion — after the first decode compile,
    admission/retire churn through the scheduler adds zero compile
    events — plus the tick-phase span vocabulary."""
    from neural_networks_parallel_training_with_mpi_tpu.models.transformer import (
        Transformer, TransformerConfig,
    )
    from neural_networks_parallel_training_with_mpi_tpu.serve import (
        Scheduler, ServeConfig,
    )
    from neural_networks_parallel_training_with_mpi_tpu.utils import prng

    model = Transformer(TransformerConfig(
        vocab_size=64, max_seq_len=64, n_layers=2, d_model=32, n_heads=4,
        d_ff=64))
    params = model.init(prng.init_key(0))
    sched = Scheduler(model, params, ServeConfig(
        slots=2, num_blocks=24, block_size=8, prefill_chunk=8,
        trace_dir=str(tmp_path / "trace")))
    first = sched.submit([1, 2, 3], 4)
    sched.run_until_drained()
    n_events = len(ledger_lib.active().events)
    assert len(ledger_lib.active().events_for("serve_decode")) == 1
    # churn: staggered admits/retires, new tables, block growth across
    # boundaries (3 + 8 > block_size) — same prefill bucket width, so
    # the WHOLE ledger must stay flat: zero new compile events
    for n_new in (6, 3, 8):
        sched.submit([1, 2, 3], n_new)
        sched.tick()
    sched.run_until_drained()
    assert len(ledger_lib.active().events) == n_events, (
        "table churn recompiled: "
        f"{ledger_lib.active().events[n_events:]}")
    sched.close()
    names = {s["name"] for s in _spans(str(tmp_path / "trace"))}
    assert {"admit", "prefill", "decode", "retire"} <= names
    assert sched.result(first)  # tokens still flow through the seam
    assert trace_lib.active() is None  # close() released the tracer


# ---------------------------------------------------------------------------
# RL wiring
# ---------------------------------------------------------------------------

def test_rl_runner_traces_dispatch_and_step_compile(tmp_path, mesh8):
    from neural_networks_parallel_training_with_mpi_tpu.rl.runner import (
        RLRunner,
    )

    cfg = _cfg(tmp_path, workload="rl")
    cfg.rl.n_envs = 16
    cfg.rl.rollout_steps = 4
    cfg.rl.total_updates = 3
    r = RLRunner(cfg, mesh=mesh8)
    res = r.fit()
    assert np.isfinite(res["final_loss"])
    tdir = os.path.join(cfg.telemetry_dir, "trace")
    assert _spans(tdir, "dispatch")
    comps = _compiles(tdir)
    assert any(c["name"] == "rl_anakin_step" for c in comps)


# ---------------------------------------------------------------------------
# trace_report: merge semantics + stdlib-only proof
# ---------------------------------------------------------------------------

def _write_synthetic(tmp_path):
    """Two processes x two incarnations of one run, with a compile
    ledger file — the shape a supervised 2-process crash/relaunch
    leaves behind."""
    t0 = 1_700_000_000.0
    for p in (0, 1):
        for inc in (0, 1):
            path = tmp_path / f"trace-p{p}-i{inc}.jsonl"
            base = t0 + inc * 10.0  # 10s relaunch gap
            recs = [{"kind": "meta", "t": base, "p": p, "run": "R",
                     "inc": inc}]
            for i in range(3):
                recs.append({"kind": "span", "name": "dispatch",
                             "t": base + i, "dur": 0.5, "p": p,
                             "run": "R", "inc": inc, "step": i})
            recs.append({"kind": "span", "name": "ckpt", "t": base + 3,
                         "dur": 0.2, "p": p, "run": "R", "inc": inc})
            path.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
    (tmp_path / "compiles-p0-i1.jsonl").write_text(json.dumps(
        {"kind": "compile", "name": "train_step[dp]", "n_compile": 1,
         "t": t0 + 10.0, "compile_ms": 1500.0, "lower_ms": 100.0,
         "p": 0, "run": "R", "inc": 1,
         "signature": {"[0]": "float32[4]"}}) + "\n")


def test_trace_report_merges_processes_and_incarnations(tmp_path):
    """Acceptance shape: both processes and both incarnations land on
    ONE timeline, correlated by run_id, with the relaunch gap visible."""
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import importlib

        tr = importlib.import_module("trace_report")
    finally:
        sys.path.pop(0)
    _write_synthetic(tmp_path)
    rc = tr.main([str(tmp_path), "--json"])
    assert rc == 0
    chrome = json.load(open(tmp_path / "trace.json"))
    names = {e["args"]["name"] for e in chrome["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert names == {f"proc {p} / incarnation {i} [R]"
                     for p in (0, 1) for i in (0, 1)}
    xs = [e for e in chrome["traceEvents"] if e.get("ph") == "X"]
    assert len(xs) == 16  # 4 groups x 4 spans, one shared axis
    summary = tr.summarize(tr.load_dir(str(tmp_path)))
    gaps = {(g["process"], g["from_incarnation"]): g["gap_s"]
            for g in summary["relaunch_gaps"]}
    assert gaps[(0, 0)] == pytest.approx(6.8) and (1, 0) in gaps
    comp = summary["compiles"][0]
    assert comp["incarnation"] == 1 and comp["compile_s"] == 1.5


def test_trace_report_is_stdlib_only(tmp_path):
    """python -S (no site-packages): the merge tool must run on a jax-
    less ops host (ckpt_fsck/metrics_summary precedent)."""
    _write_synthetic(tmp_path)
    out = subprocess.run([sys.executable, "-S", str(REPORT),
                          str(tmp_path)],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "relaunch gap" in out.stdout
    assert "proc 1 / incarnation 1" in out.stdout


def test_metrics_summary_trace_view(tmp_path):
    """Satellite: one tool still summarizes a run end-to-end —
    metrics_summary --trace appends the per-phase/compile rollup."""
    run = tmp_path / "run"
    trace_dir = run / "trace"
    trace_dir.mkdir(parents=True)
    (run / "metrics.jsonl").write_text(json.dumps(
        {"step": 1, "loss": 0.5, "kind": "step"}) + "\n")
    _write_synthetic(trace_dir)
    out = subprocess.run([sys.executable,
                          str(REPO / "tools" / "metrics_summary.py"),
                          str(run), "--trace"],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "records: 1" in out.stdout
    assert "dispatch" in out.stdout and "compiles:" in out.stdout
    jout = subprocess.run([sys.executable,
                           str(REPO / "tools" / "metrics_summary.py"),
                           str(run), "--trace", "--json"],
                          capture_output=True, text=True, timeout=60)
    doc = json.loads(jout.stdout)
    assert doc["trace"]["runs"] == ["R"]


def test_supervisor_stamps_run_identity():
    """The supervisor hands every child ONE stable run_id and its
    attempt number as the incarnation — the correlation channel the
    merged timeline keys on."""
    from neural_networks_parallel_training_with_mpi_tpu.train import (
        resilience,
    )

    envs = []
    codes = iter([1, 1, 0])

    def fake_call(cmd, env=None):
        envs.append(dict(env))
        return next(codes)

    orig = resilience.subprocess.call
    resilience.subprocess.call = fake_call
    try:
        rc = resilience.supervise(["x"], max_restarts=5, backoff=0.0,
                                  log=lambda m: None,
                                  _sleep=lambda s: None)
    finally:
        resilience.subprocess.call = orig
    assert rc == 0
    incs = [e[resilience.INCARNATION_ENV] for e in envs]
    assert incs == ["0", "1", "2"]
    runs = {e[resilience.RUN_ID_ENV] for e in envs}
    assert len(runs) == 1 and next(iter(runs))


# ---------------------------------------------------------------------------
# supervised crash -> relaunch: the merged-timeline acceptance runs
# ---------------------------------------------------------------------------

def _clean_env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("NNPT_FAULTS", None)
    for k in (trace_lib.RUN_ID_ENV, trace_lib.INCARNATION_ENV):
        env.pop(k, None)
    env["JAX_PLATFORMS"] = "cpu"
    return env


@pytest.mark.chaos
def test_supervised_crash_relaunch_merges_incarnations(tmp_path):
    """A supervised single-process run crashes mid-training and
    relaunches: the trace dir holds one file per incarnation, all
    sharing the supervisor's run_id, and trace_report puts both on one
    timeline with the relaunch gap visible."""
    marker = tmp_path / "crashed"
    trace_dir = tmp_path / "trace"
    out = subprocess.run(
        [sys.executable, "-m",
         "neural_networks_parallel_training_with_mpi_tpu",
         "--platform", "cpu", "--num_devices", "2", "--dataset",
         "regression", "--n_samples", "32", "--batch_size", "8",
         "--no-full-batch", "--nepochs", "4",
         "--checkpoint_dir", str(tmp_path / "ck"),
         "--checkpoint_every", "3",
         "--trace_dir", str(trace_dir),
         "--faults", f"crash@9?once={marker}",
         "--supervise", "2", "--supervise_backoff", "0.1"],
        capture_output=True, text=True, timeout=360, env=_clean_env(),
        cwd=str(REPO))
    text = out.stdout + out.stderr
    assert out.returncode == 0, text[-3000:]
    assert marker.exists()
    files = sorted(os.listdir(trace_dir))
    assert any("-i0.jsonl" in f for f in files), files
    assert any("-i1.jsonl" in f for f in files), files
    spans = _spans(str(trace_dir))
    runs = {s["run"] for s in spans}
    assert len(runs) == 1  # supervisor-stamped, stable across relaunch
    incs = {s["inc"] for s in spans}
    assert {0, 1} <= incs
    summary_out = subprocess.run(
        [sys.executable, "-S", str(REPORT), str(trace_dir)],
        capture_output=True, text=True, timeout=60)
    assert summary_out.returncode == 0, summary_out.stderr
    assert "relaunch gap" in summary_out.stdout
    assert (trace_dir / "trace.json").exists()


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.chaos
@pytest.mark.slow
def test_two_process_crash_relaunch_one_timeline(tmp_path):
    """ACCEPTANCE: a supervised 2-process world where process 1 crashes
    mid-training; both supervisors relaunch, the world re-forms, the run
    completes — and ONE merged Perfetto trace.json carries spans from
    BOTH processes and BOTH incarnations, correlated by run_id, with the
    relaunch gap visible."""
    port = _free_port()
    trace_dir = tmp_path / "trace"
    marker = tmp_path / "crashed"
    common = ["--platform", "cpu", "--dataset", "regression",
              "--n_samples", "32", "--batch_size", "8", "--no-full-batch",
              "--nepochs", "8", "--checkpoint_dir", str(tmp_path / "ck"),
              "--checkpoint_every", "2", "--trace_dir", str(trace_dir),
              "--hang_timeout", "15", "--collective_timeout", "10",
              "--supervise", "4", "--supervise_backoff", "0.3",
              "--supervise_backoff_max", "2"]

    def env_for(pid):
        env = _clean_env()
        env["COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
        env["NNPT_NUM_PROCESSES"] = "2"
        env["NNPT_PROCESS_ID"] = str(pid)
        env["NNPT_WORLD_TIMEOUT_S"] = "30"
        # ONE job-wide run id, set by the operator like the coordinator
        # address — each process's supervisor inherits it
        env[trace_lib.RUN_ID_ENV] = "acceptance-run"
        return env

    pkg = "neural_networks_parallel_training_with_mpi_tpu"
    p0 = subprocess.Popen([sys.executable, "-m", pkg, *common],
                          stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, text=True,
                          env=env_for(0), cwd=str(REPO))
    p1 = subprocess.Popen([sys.executable, "-m", pkg, *common,
                           "--faults", f"crash@7?once={marker}"],
                          stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, text=True,
                          env=env_for(1), cwd=str(REPO))
    try:
        out0, _ = p0.communicate(timeout=420)
        out1, _ = p1.communicate(timeout=420)
    except subprocess.TimeoutExpired:
        p0.kill()
        p1.kill()
        pytest.fail("2-process crash/relaunch scenario did not complete")
    assert marker.exists(), out1[-2000:]
    assert p0.returncode == 0, out0[-3000:]
    assert p1.returncode == 0, out1[-3000:]
    spans = _spans(str(trace_dir))
    assert {s["run"] for s in spans} == {"acceptance-run"}
    procs = {s["p"] for s in spans}
    incs = {s["inc"] for s in spans}
    assert procs == {0, 1}, procs          # both processes...
    assert {0, 1} <= incs, incs            # ...and both incarnations
    # the crashed process's relaunch starts strictly after its first
    # incarnation ends: the gap is visible on the shared clock
    p1_spans = [s for s in spans if s["p"] == 1]
    i0_end = max(s["t"] + s["dur"] for s in p1_spans if s["inc"] == 0)
    i1_start = min(s["t"] for s in p1_spans if s["inc"] >= 1)
    assert i1_start > i0_end
    # one merged Perfetto-loadable timeline
    rep = subprocess.run([sys.executable, "-S", str(REPORT),
                          str(trace_dir), "--json"],
                         capture_output=True, text=True, timeout=60)
    assert rep.returncode == 0, rep.stderr
    summary = json.loads(rep.stdout)
    assert summary["runs"] == ["acceptance-run"]
    assert any(g["gap_s"] > 0 for g in summary["relaunch_gaps"])
    chrome = json.load(open(trace_dir / "trace.json"))
    metas = {e["args"]["name"] for e in chrome["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert any("proc 0" in m for m in metas)
    assert any("proc 1" in m for m in metas)
    assert any("incarnation 1" in m for m in metas)
