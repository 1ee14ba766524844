"""Fleet observability plane (DESIGN.md §7): rollup sketch snapshots,
cross-process aggregation, SLO burn-rate alerting, per-request flow
traces, per-role heartbeats.

Pins, by acceptance criterion:

* **rollups**: trainer and serving scheduler emit ``kind="rollup"``
  records carrying SERIALIZED sketch state + the (process, run,
  incarnation) identity; a final rollup lands at flush/close.
* **alerts**: a nan-poisoned loss raises ``loss_nonfinite``; missed
  deadlines past the error budget raise ``slo_burn_rate``; ``alerts``
  off silences both; the supervisor summarizes a child's alerts next
  to its exit (observe-and-annotate).
* **fleet merge**: ``tools/obs_agg.py`` merges N dirs into fleet.json
  whose percentiles match exact numpy within the sketches' STATED
  rank-error bound, Prometheus text exposition + the /metrics endpoint
  serve the same numbers, and a stale non-final heartbeat raises
  ``heartbeat_stale``.
* **heartbeat collision**: a trainer and a serving replica sharing one
  telemetry dir own separate ``heartbeat-<role>-p<P>.json`` files;
  legacy readers resolve through the back-compat fallback.
* **flow traces**: one request's admit -> prefill -> decode -> retire
  is a connected s/t/f flow chain in the trace, rendered as Chrome
  flow events by trace_report; a bounded tracer's dropped-span footer
  surfaces as TRUNCATED in the merged summary.

Cheap pins run in the budgeted core lane; the supervised-fault
acceptance e2e is slow/chaos.  ``-m obs`` runs the lane alone.
"""

import glob
import json
import math
import os
import pathlib
import subprocess
import sys
import time
import urllib.request

import jax
import numpy as np
import pytest

from neural_networks_parallel_training_with_mpi_tpu.config import (
    DataConfig, MeshConfig, ModelConfig, TrainConfig,
)
from neural_networks_parallel_training_with_mpi_tpu.train import (
    resilience,
    telemetry as telemetry_lib,
    trace as trace_lib,
)
from neural_networks_parallel_training_with_mpi_tpu.train.trainer import (
    Trainer,
)
from neural_networks_parallel_training_with_mpi_tpu.utils.sketches import (
    QuantileSketch,
)

pytestmark = pytest.mark.obs

REPO = pathlib.Path(__file__).resolve().parent.parent
OBS_AGG = REPO / "tools" / "obs_agg.py"


def _load_tool(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        f"_obs_{name}", str(REPO / "tools" / f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(**kw):
    base = dict(nepochs=2, full_batch=False, batch_size=8, lr=1e-3,
                momentum=0.0, data=DataConfig(n_samples=32),
                mesh=MeshConfig(data=8), metrics_every=1)
    base.update(kw)
    return TrainConfig(**base)


def _records(d):
    with open(os.path.join(d, "metrics.jsonl")) as f:
        return [json.loads(line) for line in f if line.strip()]


def _tiny_serve(tmp_path, tag="s", n_requests=25, slo_ms=None, **cfg_kw):
    from neural_networks_parallel_training_with_mpi_tpu.models.transformer import (  # noqa: E501
        Transformer, TransformerConfig,
    )
    from neural_networks_parallel_training_with_mpi_tpu.serve.scheduler import (  # noqa: E501
        Scheduler, ServeConfig,
    )
    from neural_networks_parallel_training_with_mpi_tpu.utils import prng

    model = Transformer(TransformerConfig(
        vocab_size=32, max_seq_len=64, n_layers=1, d_model=16,
        n_heads=2, d_ff=32))
    params = model.init(prng.init_key(0))
    tdir = str(tmp_path / tag)
    sched = Scheduler(model, params, ServeConfig(
        slots=2, num_blocks=24, block_size=8, telemetry_dir=tdir,
        metrics_every=2, default_slo_ms=slo_ms, **cfg_kw))
    rids = [sched.submit([1 + i % 5, 2, 3], 4) for i in range(n_requests)]
    sched.run_until_drained()
    return sched, tdir, rids


# ------------------------------------------------------------------ rollups

def test_trainer_rollups_carry_sketches_and_identity(tmp_path, mesh8,
                                                     monkeypatch):
    monkeypatch.setenv(trace_lib.RUN_ID_ENV, "r-obs")
    monkeypatch.setenv(trace_lib.INCARNATION_ENV, "2")
    d = str(tmp_path / "t")
    t = Trainer(_cfg(nepochs=4, telemetry_dir=d, rollup_every=4),
                mesh=mesh8)
    t.fit()
    recs = _records(d)
    rollups = [r for r in recs if r["kind"] == "rollup"]
    steps = [r for r in recs if r["kind"] == "step"]
    assert rollups, "no rollup records at rollup_every=4"
    last = rollups[-1]
    # identity triple (the PR 10 correlation channel) + role stamp
    assert last["run"] == "r-obs" and last["inc"] == 2
    assert last["role"] == "train" and "t_unix" in last
    # serialized sketch STATE, not point stats — and it round-trips
    # into quantiles consistent with the raw step stream
    losses = [r["loss"] for r in steps]
    sk = QuantileSketch.from_dict(last["sketches"]["loss"])
    assert sk.n == len(losses)
    assert sk.quantile(0.0) == min(losses)
    assert sk.quantile(1.0) == max(losses)
    exact = float(np.quantile(np.array(losses), 0.5,
                              method="inverted_cdf"))
    rank = sorted(losses).index(sk.quantile(0.5))
    target = math.ceil(0.5 * len(losses)) - 1
    assert abs(rank - target) <= max(
        1, math.ceil(sk.rank_error_bound * sk.n)), (exact, sk.quantile(0.5))
    assert last["counters"]["metrics_records"] == len(steps)
    # the final rollup is the flush-time snapshot: it covers ALL steps
    assert last["step"] == steps[-1]["step"]


def test_trainer_rollups_off_by_default(tmp_path, mesh8):
    d = str(tmp_path / "t")
    Trainer(_cfg(telemetry_dir=d), mesh=mesh8).fit()
    assert not [r for r in _records(d) if r["kind"] == "rollup"]


def test_params_bitwise_identical_obs_on_off(tmp_path, mesh8):
    """The plane is pure observation: sketch feeds, detectors, rollup
    serialization and the heartbeat are host arithmetic on fetched
    floats, so the parameters after the same steps are bit for bit those
    of a run with no telemetry, and of one with the metrics stream
    alone."""
    def fit(**kw):
        t = Trainer(_cfg(lr=1e-2, momentum=0.9, skip_nonfinite=True, **kw),
                    mesh=mesh8)
        t.fit()
        return jax.tree_util.tree_leaves(jax.device_get(t.state.params))

    on_dir = str(tmp_path / "on")
    bare = fit(metrics_every=0)
    stream = fit(telemetry_dir=str(tmp_path / "stream"), alerts=False)
    plane = fit(telemetry_dir=on_dir, rollup_every=2, alerts=True)
    assert [r for r in _records(on_dir) if r["kind"] == "rollup"]
    for a, b, c in zip(bare, stream, plane):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


# ------------------------------------------------------------------- alerts

def test_nonfinite_loss_alert_and_opt_out(tmp_path, mesh8):
    def run(alerts):
        d = str(tmp_path / f"t{alerts}")
        t = Trainer(_cfg(nepochs=2, skip_nonfinite=True,
                         faults="nan@3?max=1", telemetry_dir=d,
                         alerts=alerts), mesh=mesh8)
        t.fit()
        return ([r for r in _records(d) if r["kind"] == "alert"],
                t.telemetry)

    alerts, telem = run(True)
    assert any(a["alert"] == "loss_nonfinite" for a in alerts)
    a = next(a for a in alerts if a["alert"] == "loss_nonfinite")
    assert a["role"] == "train" and "t_unix" in a and a["step"] >= 3
    # the non-finite value is STRINGIFIED so the record (and any
    # fleet.json it is copied into) stays strict JSON
    assert a["value"] == "nan"
    assert json.loads(json.dumps(a, allow_nan=False))["value"] == "nan"
    # the flight recorder saw it too (a postmortem shows what fired)
    assert any(r.get("event") == "alert"
               for r in telem.recorder.records)
    assert telem.alerts_fired == len(alerts)
    off, _ = run(False)
    assert not off


def test_slo_burn_rate_alert_fires_and_is_quiet_without_slo(tmp_path):
    # 0.001ms SLO: every request misses -> burn rate >> threshold
    sched, tdir, _ = _tiny_serve(tmp_path, "hot", n_requests=25,
                                 slo_ms=0.001, rollup_every=8)
    sched.close()
    recs = _records(tdir)
    alerts = [r for r in recs if r["kind"] == "alert"]
    assert alerts and all(a["alert"] == "slo_burn_rate" for a in alerts)
    assert alerts[0]["burn_rate"] >= 2.0 and alerts[0]["role"] == "serve"
    rollup = [r for r in recs if r["kind"] == "rollup"][-1]
    assert rollup["counters"]["deadline_missed"] == 25
    assert rollup["counters"]["slo_events"] == 25
    # SLO-less requests never burn the budget
    quiet, qdir, _ = _tiny_serve(tmp_path, "quiet", n_requests=25)
    quiet.close()
    assert not [r for r in _records(qdir) if r["kind"] == "alert"]
    # ...and the sketch state still rolled up on close despite the
    # cadence never being crossed mid-run
    sched3, tdir3, _ = _tiny_serve(tmp_path, "final", n_requests=3,
                                   rollup_every=10 ** 6)
    sched3.close()
    finals = [r for r in _records(tdir3) if r["kind"] == "rollup"]
    assert len(finals) == 1 and "ttft_ms" in finals[0]["sketches"]


def test_supervise_annotates_child_alerts(tmp_path):
    metrics = tmp_path / "metrics.jsonl"
    alert = {"kind": "alert", "alert": "slo_burn_rate",
             "t_unix": round(time.time(), 3)}
    child = (f"import json; open({str(metrics)!r}, 'a').write("
             f"json.dumps({alert!r}) + '\\n'); raise SystemExit(7)")
    logs = []
    rc = resilience.supervise(
        [sys.executable, "-c", child], max_restarts=1, backoff=0.0,
        log=logs.append, alerts_path=str(metrics),
        _sleep=lambda s: None)
    assert rc == 7
    annotated = [m for m in logs if "telemetry alert(s)" in m]
    # one launch + one relaunch -> each child's alert annotated once
    assert len(annotated) == 2
    assert "slo_burn_rate x1" in annotated[0] and "observe-only" in \
        annotated[0]


# ------------------------------------------------- per-role heartbeats

def test_shared_dir_heartbeats_do_not_collide(tmp_path, mesh8):
    d = str(tmp_path / "shared")
    Trainer(_cfg(telemetry_dir=d), mesh=mesh8).fit()
    sched, _, _ = _tiny_serve(tmp_path, "unused", n_requests=2)
    # point the serving telemetry at the SAME dir (two writers, one dir)
    sched.close()
    sched2, tdir2, _ = _tiny_serve(pathlib.Path(d).parent, "shared",
                                   n_requests=2)
    sched2.close()
    assert tdir2 == d
    names = sorted(os.path.basename(p)
                   for p in glob.glob(os.path.join(d, "heartbeat*.json")))
    assert names == ["heartbeat-serve-p0.json", "heartbeat-train-p0.json"]
    # each file carries ITS writer's final step — no last-writer-wins
    train_hb = json.load(open(os.path.join(d, names[1])))
    serve_hb = json.load(open(os.path.join(d, names[0])))
    assert train_hb["step"] == 8           # 2 epochs x 4 steps
    assert serve_hb["step"] == sched2.tick_no
    # back-compat reads: the legacy shared path resolves to the
    # freshest role file, for both the age and the document
    legacy = os.path.join(d, "heartbeat.json")
    assert not os.path.exists(legacy)
    assert resilience.heartbeat_age_s(legacy) is not None
    assert telemetry_lib.read_heartbeat(legacy) == serve_hb
    # a directory path works too (obs_agg/metrics_summary convention)
    assert resilience.heartbeat_age_s(d) is not None
    # staleness stays PER ROLE: age the serve file artificially and the
    # train file still reads fresh through its exact path
    old = time.time() - 1000
    os.utime(os.path.join(d, names[0]), (old, old))
    assert resilience.heartbeat_age_s(
        os.path.join(d, names[0])) > 900
    assert resilience.heartbeat_age_s(
        os.path.join(d, names[1])) < 900
    # ...and the legacy fallback reports the freshest (train) one
    assert resilience.heartbeat_age_s(legacy) < 900
    # a MISSING role-qualified path never falls back to a sibling: the
    # hang monitor must not read a co-resident process's beats as its
    # own child's health (that would re-create the collision blindness)
    assert resilience.heartbeat_age_s(
        os.path.join(d, "heartbeat-train-p7.json")) is None
    assert resilience.heartbeat_filename("train", 0) == \
        "heartbeat-train-p0.json"


# ------------------------------------------------------- flow traces

def test_request_flow_chain_and_chrome_export(tmp_path):
    trace_dir = str(tmp_path / "trace")
    sched, tdir, rids = _tiny_serve(tmp_path, "flow", n_requests=3,
                                    trace_dir=trace_dir)
    sched.close()
    flows = []
    for p in glob.glob(os.path.join(trace_dir, "trace-*.jsonl")):
        for line in open(p):
            rec = json.loads(line)
            if rec.get("kind") == "flow":
                flows.append(rec)
    rid = rids[0]
    chain = [f for f in flows if f.get("rid") == rid]
    phases = [f["fph"] for f in chain]
    stages = [f.get("stage") for f in chain]
    # admit starts the flow, prefill chunks + decode ticks step it,
    # retire finishes it — one connected arrow path per request
    assert phases[0] == "s" and phases[-1] == "f"
    assert stages[0] == "admit" and stages[-1] == "retire"
    assert "prefill" in stages and "decode" in stages
    assert all(p == "t" for p in phases[1:-1])
    assert len({f["id"] for f in chain}) == 1
    # trace_report renders them as Chrome flow events bound to slices
    tr = _load_tool("trace_report")
    data = tr.load_dir(trace_dir)
    chrome = tr.to_chrome(data)
    evs = [e for e in chrome["traceEvents"]
           if e.get("cat") == "flow" and e.get("id") ==
           chain[0]["id"]]
    assert [e["ph"] for e in evs] == phases
    assert evs[-1]["bp"] == "e"
    summary = tr.summarize(data)
    assert summary["groups"][0]["n_flows"] == len(flows)


def test_trace_report_surfaces_dropped_footer(tmp_path):
    tracer = trace_lib.Tracer(str(tmp_path), process_id=1, run_id="r",
                              incarnation=0, max_events=5)
    trace_lib.install(tracer)
    try:
        for i in range(9):
            with trace_lib.span("tick", i=i):
                pass
    finally:
        trace_lib.stop_run(tracer)
    tr = _load_tool("trace_report")
    summary = tr.summarize(tr.load_dir(str(tmp_path)))
    g = summary["groups"][0]
    assert g["n_spans"] == 5 and g["dropped_spans"] == 4
    assert summary["dropped_spans_total"] == 4
    text = tr.render_text(summary)
    assert "TRUNCATED: 4 span(s)" in text


# ------------------------------------------------------ fleet aggregation

def _write_rollup_dir(tmp_path, tag, role, samples, p=0, inc=0,
                      counters=None, gauges=None, run="r-fleet"):
    """A telemetry dir containing one hand-built rollup (the aggregator
    contract is the record schema, not the writer)."""
    d = tmp_path / tag
    d.mkdir(exist_ok=True)
    sk = QuantileSketch()
    for v in samples:
        sk.add(float(v))
    rec = {"kind": "rollup", "role": role, "step": len(samples),
           "t": 1.0, "t_unix": round(time.time(), 3), "p": p,
           "inc": inc, "run": run,
           "sketches": {"ttft_ms": sk.to_dict()},
           "counters": dict(counters or {}),
           "gauges": {k: {"last": v, "t": time.time(), "min": v,
                          "max": v} for k, v in (gauges or {}).items()}}
    with open(d / "metrics.jsonl", "a") as f:
        f.write(json.dumps(rec) + "\n")
    return str(d)


def test_obs_agg_merges_within_stated_bound(tmp_path):
    agg = _load_tool("obs_agg")
    rng = np.random.default_rng(5)
    shards = [rng.lognormal(3.0, 1.0, int(rng.integers(100, 800)))
              for _ in range(3)]
    dirs = [
        _write_rollup_dir(tmp_path, f"d{i}", "serve", s, p=i,
                          counters={"tokens_out": 100 * (i + 1)},
                          gauges={"tokens_per_sec": 50.0 * (i + 1)})
        for i, s in enumerate(shards)]
    doc = agg.aggregate(dirs)
    serve = doc["roles"]["serve"]
    assert serve["writers"] == 3
    merged = serve["sketches"]["ttft_ms"]
    data = np.sort(np.concatenate(shards))
    n = len(data)
    assert merged["n"] == n
    bound = merged["rank_error_bound"]
    assert bound <= 0.0101  # one K-way merge level: 2 * eps
    for q_name, q in (("p50", 0.5), ("p99", 0.99)):
        ans = merged[q_name]
        lo = np.searchsorted(data, ans, side="left") + 1
        hi = np.searchsorted(data, ans, side="right")
        target = max(1, math.ceil(q * n))
        err = (0 if lo <= target <= hi
               else min(abs(lo - target), abs(hi - target))) / n
        assert err <= bound + 1.0 / n, (q_name, err, bound)
    # counters SUM across identities; additive gauges sum too
    # (100+200+300 tokens; 50+100+150 tok/s)
    assert serve["counters"]["tokens_out"] == 600
    assert serve["gauges"]["tokens_per_sec"] == 300.0
    assert doc["fleet"]["tokens_per_sec"] == 300.0


def test_obs_agg_gauges_only_from_latest_incarnation(tmp_path):
    agg = _load_tool("obs_agg")
    d = _write_rollup_dir(tmp_path, "d", "serve", [1.0], inc=0,
                          counters={"tokens_out": 100},
                          gauges={"tokens_per_sec": 999.0})
    _write_rollup_dir(tmp_path, "d", "serve", [2.0, 3.0], inc=1,
                      counters={"tokens_out": 40},
                      gauges={"tokens_per_sec": 10.0})
    doc = agg.aggregate([d])
    serve = doc["roles"]["serve"]
    # counters: both incarnations' work happened -> 140; gauges: only
    # the live incarnation's rate is current load -> 10, not 1009
    assert serve["counters"]["tokens_out"] == 140
    assert serve["gauges"]["tokens_per_sec"] == 10.0
    # sketches merge across incarnations (all that latency was served)
    assert serve["sketches"]["ttft_ms"]["n"] == 3


def test_obs_agg_heartbeat_stale_alert_and_window(tmp_path):
    agg = _load_tool("obs_agg")
    d = _write_rollup_dir(tmp_path, "d", "serve", [1.0, 2.0])
    hb = os.path.join(d, "heartbeat-serve-p0.json")
    json.dump({"step": 5}, open(hb, "w"))
    old = time.time() - 500
    os.utime(hb, (old, old))
    # an EXPIRED alert record must fall out of the fleet window
    with open(os.path.join(d, "metrics.jsonl"), "a") as f:
        f.write(json.dumps({"kind": "alert", "alert": "loss_zscore",
                            "t_unix": time.time() - 9999}) + "\n")
    doc = agg.aggregate([d], stale_after_s=120.0, alert_window_s=3600.0)
    assert doc["alerts"]["by_name"] == {"heartbeat_stale": 1}
    stale = doc["alerts"]["recent"][-1]
    assert stale["age_s"] > 400 and stale["role"] == "serve"
    # a FINAL heartbeat is a finished run, not a stale one
    json.dump({"step": 5, "final": True}, open(hb, "w"))
    os.utime(hb, (old, old))
    doc2 = agg.aggregate([d], stale_after_s=120.0)
    assert doc2["alerts"]["n"] == 0


def test_obs_agg_fleet_json_prometheus_and_http(tmp_path):
    agg = _load_tool("obs_agg")
    d = _write_rollup_dir(tmp_path, "d", "serve", [10.0, 20.0, 30.0],
                          counters={"tokens_out": 7},
                          gauges={"queue_depth": 2.0})
    out = tmp_path / "fleet.json"
    prom_path = tmp_path / "fleet.prom"
    rc = agg.main([d, "--out", str(out), "--prom", str(prom_path)])
    assert rc == 0
    fleet = json.load(open(out))
    assert fleet["roles"]["serve"]["sketches"]["ttft_ms"]["p50"] == 20.0
    prom = open(prom_path).read()
    assert "# TYPE nnpt_ttft_ms summary" in prom
    assert 'nnpt_ttft_ms{role="serve",quantile="0.99"} 30.0' in prom
    assert 'nnpt_tokens_out_total{role="serve"} 7' in prom
    # gauges live in a '_current' family disjoint from any summary of
    # the same series (one family must not mix sample types)
    assert 'nnpt_queue_depth_current{role="serve"} 2.0' in prom
    # the optional http.server endpoint serves the same two documents
    server = agg.make_http_server(0, lambda: agg.aggregate([d]))
    import threading

    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        port = server.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read()
        assert b"nnpt_ttft_ms" in body
        fleet_doc = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/fleet.json", timeout=10).read())
        assert fleet_doc["roles"]["serve"]["counters"]["tokens_out"] == 7
    finally:
        server.shutdown()
        server.server_close()
    # dashboard rendering is plain text over the same doc
    text = agg.render_dashboard(agg.aggregate([d]))
    assert "NNPT FLEET" in text and "ttft" in text


def test_obs_agg_python_S_smoke(tmp_path):
    """python -S (no site-packages): the aggregator must run on a
    jax-less ops host — the ckpt_fsck convention, wired into the core
    lane."""
    d = _write_rollup_dir(tmp_path, "d", "serve", [1.0, 2.0, 3.0])
    out = subprocess.run(
        [sys.executable, "-S", str(OBS_AGG), d, "--json"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    doc = json.loads(out.stdout)
    assert doc["roles"]["serve"]["sketches"]["ttft_ms"]["n"] == 3
    miss = subprocess.run(
        [sys.executable, "-S", str(OBS_AGG), str(tmp_path / "nope")],
        capture_output=True, text=True, timeout=120)
    assert miss.returncode == 2


# ----------------------------------------- metrics_summary composition

def test_metrics_summary_composes_alert_and_rollup_views(tmp_path,
                                                         capsys):
    sched, tdir, _ = _tiny_serve(tmp_path, "ms", n_requests=25,
                                 slo_ms=0.001, rollup_every=8)
    sched.close()
    ms = _load_tool("metrics_summary")
    capsys.readouterr()  # drain the serve run's own log lines
    assert ms.main([tdir, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["alerts"]["by_name"].get("slo_burn_rate", 0) >= 1
    assert doc["rollups"]["serve"]["sketches"]["ttft_ms"]["n"] == 25
    assert doc["rollups"]["serve"]["counters"]["deadline_missed"] == 25
    assert doc["heartbeat"]["final"] is True  # per-role file resolved
    # text render names the alerts and the rollup percentiles
    assert ms.main([tdir]) == 0
    text = capsys.readouterr().out
    assert "ALERTS:" in text and "slo_burn_rate" in text
    assert "rollups [serve]" in text and "ttft_ms" in text


# ------------------------------------------------- acceptance e2e (chaos)

@pytest.mark.slow
@pytest.mark.chaos
def test_fleet_acceptance_train_fault_plus_serving(tmp_path):
    """The ISSUE 14 acceptance path: a supervised training run with an
    injected nan fault and a concurrent serving loadgen run, each with
    its own telemetry dir, aggregate via tools/obs_agg.py into one
    fleet.json whose merged serving percentiles match single-process
    ground truth within the sketch's stated bound; the anomaly's alert
    is visible in the fleet view and the Prometheus exposition; a
    request flow chain exists in the serving trace."""
    train_dir = tmp_path / "telem_train"
    serve_dir = tmp_path / "telem_serve"
    trace_dir = str(tmp_path / "serve_trace")
    env = {k: v for k, v in os.environ.items()
           if k not in ("NNPT_RUN_ID", "NNPT_INCARNATION")}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    out = subprocess.run(
        [sys.executable, "-m",
         "neural_networks_parallel_training_with_mpi_tpu",
         "--platform", "cpu", "--num_devices", "2", "--dataset",
         "regression", "--n_samples", "32", "--batch_size", "8",
         "--no-full-batch", "--nepochs", "4", "--skip-nonfinite",
         "--faults", "nan@5?max=1", "--telemetry_dir", str(train_dir),
         "--rollup_every", "4", "--checkpoint_dir",
         str(tmp_path / "ck"), "--supervise", "1",
         "--supervise_backoff", "0.1"],
        capture_output=True, text=True, timeout=240, env=env,
        cwd=str(REPO))
    assert out.returncode == 0, (out.stdout + out.stderr)[-3000:]

    # concurrent serving workload with its own dir + flow trace
    from neural_networks_parallel_training_with_mpi_tpu.serve import (
        loadgen,
    )
    from neural_networks_parallel_training_with_mpi_tpu.models.transformer import (  # noqa: E501
        Transformer, TransformerConfig,
    )
    from neural_networks_parallel_training_with_mpi_tpu.serve.scheduler import (  # noqa: E501
        Scheduler, ServeConfig,
    )
    from neural_networks_parallel_training_with_mpi_tpu.utils import prng

    model = Transformer(TransformerConfig(
        vocab_size=64, max_seq_len=64, n_layers=1, d_model=16,
        n_heads=2, d_ff=32))
    params = model.init(prng.init_key(0))
    sched = Scheduler(model, params, ServeConfig(
        slots=4, num_blocks=48, block_size=8,
        telemetry_dir=str(serve_dir), metrics_every=4, rollup_every=16,
        trace_dir=trace_dir, default_slo_ms=0.001))
    row = loadgen.run_closed_loop(sched, clients=4,
                                  requests_per_client=8, vocab_size=64)
    truth = sorted(s.ttft_ms for s in
                   [sched.stats(r) for r in range(sched.completed)]
                   if s.ttft_ms is not None)
    sched.close()

    agg = _load_tool("obs_agg")
    fleet_path = tmp_path / "fleet.json"
    prom_path = tmp_path / "fleet.prom"
    rc = agg.main([str(train_dir), str(serve_dir), "--out",
                   str(fleet_path), "--prom", str(prom_path)])
    assert rc == 0
    fleet = json.load(open(fleet_path))
    # both roles merged into one fleet view
    assert set(fleet["roles"]) == {"serve", "train"}
    # merged serving percentiles vs single-process ground truth, within
    # the sketch's stated rank-error bound
    merged = fleet["roles"]["serve"]["sketches"]["ttft_ms"]
    n = len(truth)
    assert merged["n"] == n == row["requests"]
    bound = merged["rank_error_bound"]
    for q_name, q in (("p50", 0.5), ("p99", 0.99)):
        ans = merged[q_name]
        arr = np.asarray(truth)
        lo = np.searchsorted(arr, ans, side="left") + 1
        hi = np.searchsorted(arr, ans, side="right")
        target = max(1, math.ceil(q * n))
        err = (0 if lo <= target <= hi
               else min(abs(lo - target), abs(hi - target))) / n
        assert err <= bound + 1.0 / n, (q_name, ans, err, bound)
    # the train sketches rode the rollups into the fleet view (MFU is
    # TPU-only: off the chip its records are null and never sketched)
    assert "step_time_ms" in fleet["roles"]["train"]["sketches"]
    assert "mfu" not in fleet["roles"]["train"]["sketches"]
    # the training anomaly and the SLO burn are fleet-visible alerts
    assert fleet["alerts"]["by_name"].get("loss_nonfinite")
    assert fleet["alerts"]["by_name"].get("slo_burn_rate")
    prom = open(prom_path).read()
    assert "nnpt_alerts_by_name{alert=\"loss_nonfinite\"}" in prom
    assert "nnpt_ttft_ms{role=\"serve\",quantile=\"0.99\"}" in prom
    # one request's full flow chain exists in the serving trace
    flows = []
    for p in glob.glob(os.path.join(trace_dir, "trace-*.jsonl")):
        for line in open(p):
            rec = json.loads(line)
            if rec.get("kind") == "flow" and rec.get("rid") == 0:
                flows.append(rec)
    phases = [f["fph"] for f in flows]
    assert phases[0] == "s" and phases[-1] == "f" and "t" in phases
    # the supervisor's relaunch log annotated the child's alerts
    assert "telemetry alert(s)" in (out.stdout + out.stderr)
