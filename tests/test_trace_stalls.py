"""Laps and stalls (train/trace.py ``LapWatch``), and the tracer that no
longer writes inside a span.

Each planted stall is one kind in a bare loop of laps and spans with lowered
thresholds, and has to be named with the right ``where`` and ``cause``; a
steady loop and the warm-up laps record none.  Then the two real loops on a
toy model (``Scheduler.tick``, ``Trainer.fit``), the tracer's writes (a
counting stand-in for the file, a fake clock), and the two tools that render
the records.
"""

import gc
import glob
import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np
import pytest

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
STEADY_S = 0.002        # a healthy lap of the bare loop on the real clock
FLOOR_S = 0.15          # the lowered floor


@pytest.fixture(autouse=True)
def _clean_trace_state():
    yield
    trace_lib.stop_run()
    ledger_lib.install(None)


def _records(trace_dir, name=None):
    out = []
    for path in sorted(glob.glob(os.path.join(trace_dir, "trace-*.jsonl"))):
        out += [json.loads(line) for line in open(path)]
    return [r for r in out if name is None or r.get("name") == name]


def _loop(watch, laps, body, between=None):
    """``laps`` iterations of: lap mark, a ``work`` span over ``body(i)``
    and a steady sleep, then ``between(i)`` outside every span."""
    for i in range(laps):
        watch.lap(i)
        with trace_lib.span("work", i=i):
            body(i)
            time.sleep(STEADY_S)
        if between is not None:
            between(i)
    return watch.end()


def _watch(**kw):
    return trace_lib.LapWatch("bare", **{"floor_s": FLOOR_S, "warmup": 8,
                                         **kw})


def _busy(seconds):
    t = time.perf_counter()
    while time.perf_counter() - t < seconds:
        pass


def _cyclic_heap(pairs=150_000):
    heap = []
    for _ in range(pairs):
        a, b = [], []
        a.append(b)
        b.append(a)
        heap.append(a)
    return heap


# ---------------------------------------------------------------------------
# a bare loop: one planted stall of each kind
# ---------------------------------------------------------------------------
# What the process really did (slept, spun, collected) is read from the OS, so
# those three run on the real clock, with a floor well above a loaded host's
# jitter, and look their record up by its lap.  Where only the times matter
# the clock is a fake one, and the loop is exact.

def _planted(watch, n):
    (rec,) = [r for r in watch.records if r["n"] == n]
    return rec


@pytest.fixture
def clock(monkeypatch):
    now = [5000.0]
    monkeypatch.setattr(trace_lib.time, "perf_counter", lambda: now[0])
    trace_lib._CLOSED.clear()   # spans of an earlier test's clock
    yield now
    trace_lib._CLOSED.clear()


def _fake_loop(watch, clock, laps, inside=None, between=None, child=None):
    """Laps of 10 ms under a ``work`` span on the fake clock; ``inside`` /
    ``between`` / ``child`` give lap ``i`` extra seconds inside the span,
    after it, and inside a child span."""
    extra = lambda f, i: (f(i) or 0.0) if f is not None else 0.0  # noqa: E731
    for i in range(laps):
        watch.lap(i)
        with trace_lib.span("work", i=i):
            clock[0] += 0.01 + extra(inside, i)
            if child is not None:
                with trace_lib.span("work/child"):
                    clock[0] += extra(child, i)
        clock[0] += extra(between, i)
    return watch.end()


def test_a_sleep_inside_a_span_is_waiting_under_that_span(tmp_path):
    tracer = trace_lib.start_run(str(tmp_path), ledger=False)
    seen = []
    listener = lambda n, t, d, a: seen.append((n, d, dict(a)))  # noqa: E731
    trace_lib.add_listener(listener)
    try:
        watch = _watch()
        lines = _loop(watch, 30,
                      lambda i: time.sleep(0.5) if i == 20 else None)
    finally:
        trace_lib.remove_listener(listener)
    rec = _planted(watch, 20)
    assert (rec["where"], rec["cause"]) == ("work", "waiting")
    assert rec["cpu_s"] < 0.1 and rec["where_s"] >= 0.5
    assert 0.4 < rec["excess_s"] < rec["wall_s"] < 1.5
    assert rec["median_s"] < 0.05
    assert rec["nvcsw"] >= 1 and rec["compiles"] == 0
    assert watch.stalls == len(watch.records)
    assert watch.stall_s == pytest.approx(
        sum(r["excess_s"] for r in watch.records), abs=1e-4)
    # the record went the way of every span: listener and file
    (stall,) = [s for s in seen if s[0] == "stall" and s[2]["n"] == 20]
    assert stall[1] == pytest.approx(rec["wall_s"], abs=1e-5)
    assert stall[2]["loop"] == "bare" and stall[2]["cause"] == "waiting"
    assert "wall_s" not in stall[2]     # the span's duration says it
    trace_lib.stop_run(tracer)
    (on_disk,) = [r for r in _records(str(tmp_path), "stall")
                  if r["n"] == 20]
    assert on_disk["where"] == "work"
    assert on_disk["dur"] == pytest.approx(rec["wall_s"], abs=1e-5)
    # one line for the log, and only once
    (line,) = [l for l in lines if l.startswith("[trace] stall: bare 20, ")]
    assert "in work 0.5" in line and "waiting: cpu 0.0" in line
    assert watch.end() == []


def test_a_busy_loop_is_python():
    watch = _watch()
    _loop(watch, 30, lambda i: _busy(0.5) if i == 15 else None)
    rec = _planted(watch, 15)
    assert (rec["where"], rec["cause"]) == ("work", "python")
    assert rec["cpu_s"] >= 0.5 * rec["wall_s"]


def test_a_collection_of_a_large_cyclic_heap_is_gc():
    watch = _watch(floor_s=0.02)
    gc.collect()
    gc.disable()    # the one collection is the planted one, of generation 2
    try:
        heap = [_cyclic_heap()]
        _loop(watch, 30,
              lambda i: (heap.clear(), gc.collect()) if i == 12 else None)
    finally:
        gc.enable()
    rec = _planted(watch, 12)
    assert (rec["where"], rec["cause"]) == ("work", "gc")
    assert rec["gc_gen"] == 2 and rec["gc_s"] >= 0.5 * rec["excess_s"]


def test_a_sleep_between_laps_is_between_laps(clock):
    watch = _watch()
    _fake_loop(watch, clock, 30, between=lambda i: 0.3 if i == 25 else 0)
    (rec,) = watch.records
    assert (rec["n"], rec["where"], rec["cause"]) == (25, "between laps",
                                                     "waiting")
    assert rec["where_s"] == pytest.approx(0.3)
    assert rec["wall_s"] == pytest.approx(0.31)
    assert rec["median_s"] == pytest.approx(0.01)
    assert rec["excess_s"] == pytest.approx(0.3)


def test_a_sleep_outside_every_span_inside_the_lap_is_no_span(clock):
    watch = _watch()
    for i in range(30):
        watch.lap(i)
        if i == 22:
            clock[0] += 0.3
        with trace_lib.span("work"):
            clock[0] += 0.01
    watch.end()
    (rec,) = watch.records
    assert (rec["n"], rec["where"]) == (22, "no span")
    assert rec["where_s"] == pytest.approx(0.3)


def test_a_sleep_in_a_child_span_names_the_child(tmp_path, clock):
    tracer = trace_lib.start_run(str(tmp_path), ledger=False)
    watch = _watch()
    _fake_loop(watch, clock, 30, child=lambda i: 0.3 if i == 18 else 0.001)
    trace_lib.stop_run(tracer)
    (rec,) = watch.records
    assert (rec["n"], rec["where"]) == (18, "work/child")
    # the parent's self time is its own 10 ms, not the child's 0.3 s
    assert rec["where_s"] == pytest.approx(0.3)
    recs = _records(str(tmp_path))
    children = [r for r in recs if r.get("name") == "work/child"]
    parents = [r for r in recs if r.get("name") == "work"]
    assert len(children) == len(parents) == 30
    assert all(r["parent"] == "work" for r in children)
    assert all("parent" not in r for r in parents)


def test_a_compile_in_the_lap_is_compile(clock):
    watch = _watch()
    for i in range(30):
        watch.lap(i)
        with trace_lib.span("decode/submit"):
            clock[0] += 0.01
            if i == 17:
                with trace_lib.span("compile:serve_decode[b4]"):
                    clock[0] += 0.2
    watch.end()
    (rec,) = watch.records
    assert rec["compiles"] == 1 and rec["cause"] == "compile"
    assert rec["where"] == "compile:serve_decode[b4]"


def test_jitter_up_to_three_medians_records_no_stall(clock):
    watch = trace_lib.LapWatch("bare", floor_s=0.0)     # the ratio alone
    scale = np.random.default_rng(0).uniform(1.0, 3.0, size=500)
    lines = _fake_loop(watch, clock, 500,
                       inside=lambda i: 0.01 * (scale[i] - 1.0))
    assert lines == [] and watch.stalls == 0 and watch.laps == 499
    assert len(watch.ring) == trace_lib.LAP_RING
    n, t_unix, wall, where, where_s = watch.ring[-1]
    assert n == 498 and where == "work"
    assert where_s == pytest.approx(wall) == pytest.approx(0.01 * scale[498])
    assert abs(t_unix - time.time()) < 60
    # and a lap of nine medians after them is one
    watch = trace_lib.LapWatch("bare", floor_s=0.0)
    _fake_loop(watch, clock, 100, inside=lambda i: 0.08 if i == 90 else 0)
    assert [r["n"] for r in watch.records] == [90]


def test_the_warm_up_laps_record_none(clock):
    watch = _watch(warmup=16)
    _fake_loop(watch, clock, 30, inside=lambda i: 0.2 if i in (3, 15) else 0)
    assert watch.stalls == 0 and not watch.records
    # the lap after them is judged
    watch = _watch(warmup=16)
    _fake_loop(watch, clock, 30, inside=lambda i: 0.2 if i == 16 else 0)
    assert [r["n"] for r in watch.records] == [16]


def test_the_open_lap_is_dropped_at_the_end_and_laps_off_the_main_thread(
        clock):
    import threading

    watch = _watch()
    _fake_loop(watch, clock, 20)
    watch.lap(20)
    clock[0] += 0.3             # what follows the loop is not a lap of it
    assert watch.end() == [] and watch.stalls == 0 and watch.laps == 19
    other = _watch()
    worker = threading.Thread(target=lambda: [other.lap(i)
                                              for i in range(5)])
    worker.start()
    worker.join(timeout=10)
    assert not worker.is_alive() and other.laps == 0


def test_the_cause_rule_in_its_order():
    base = dict(wall_s=2.0, excess_s=1.99, cpu_s=0.0, cpu_other_s=0.0,
                run_delay_s=0.0, nvcsw=3, nivcsw=0, majflt=0, gc_s=0.0,
                compiles=0)
    cause = lambda **kw: trace_lib.stall_cause({**base, **kw})  # noqa: E731
    assert cause() == "waiting"
    assert cause(cpu_other_s=1.2) == "gil"
    assert cause(run_delay_s=1.5, cpu_other_s=1.2) == "descheduled"
    assert cause(run_delay_s=None, nivcsw=4) == "descheduled"
    assert cause(run_delay_s=None, nivcsw=0) == "waiting"
    assert cause(cpu_s=1.1, run_delay_s=1.5) == "python"
    assert cause(majflt=2, cpu_s=0.3, run_delay_s=1.5) == "page_fault"
    assert cause(majflt=2, cpu_s=1.1) == "python"
    assert cause(gc_s=1.0, majflt=2, cpu_s=1.1) == "gc"
    assert cause(compiles=1, gc_s=1.9) == "compile"


# ---------------------------------------------------------------------------
# the tracer: nothing written inside a span
# ---------------------------------------------------------------------------

class CountingFile:
    """Stands where the tracer's file object stands."""

    def __init__(self, f):
        self.f, self.writes, self.flushes = f, 0, 0

    def write(self, text):
        self.writes += 1
        return self.f.write(text)

    def flush(self):
        self.flushes += 1
        return self.f.flush()

    def close(self):
        return self.f.close()


def test_closing_a_span_writes_nothing_and_a_second_brings_one_flush(
        tmp_path, clock):
    tracer = trace_lib.start_run(str(tmp_path), ledger=False, max_events=150)
    tracer._f = counting = CountingFile(tracer._f)
    watch = trace_lib.LapWatch("fake")
    for i in range(60):                 # 60 laps of 50 ms: three seconds
        watch.lap(i)
        before = (counting.writes, counting.flushes)
        with trace_lib.span("decode", tick=i):
            with trace_lib.span("decode/submit"):
                clock[0] += 0.02
        trace_lib.flow("req", f"r{i}", "t", rid=i)
        assert (counting.writes, counting.flushes) == before
        clock[0] += 0.03
    assert counting.writes == counting.flushes == 2     # at 1.0 s and 2.0 s
    on_disk = _records(str(tmp_path))
    assert 0 < len(on_disk) < 1 + 150
    assert watch.end() == [] and watch.stalls == 0
    assert counting.flushes == 3        # the loop's end wrote what waited
    trace_lib.stop_run(tracer)
    recs = _records(str(tmp_path))
    spans = [r for r in recs if r["kind"] in ("span", "flow")]
    assert len(spans) == 150            # the bound held, in order
    assert [r["tick"] for r in spans if r.get("name") == "decode"] == list(
        range(50))
    footer = recs[-1]
    assert (footer["kind"], footer["final"]) == ("meta", True)
    assert (footer["events"], footer["dropped"]) == (150, 60 * 3 - 150)


def test_four_thousand_waiting_records_are_written_before_the_second(
        tmp_path):
    tracer = trace_lib.start_run(str(tmp_path), ledger=False)
    tracer._f = counting = CountingFile(tracer._f)
    watch = trace_lib.LapWatch("burst")
    watch.lap(0)
    for i in range(trace_lib.FLUSH_RECORDS):
        with trace_lib.span("retire", tick=i):
            pass
    assert counting.writes == 0
    watch.lap(1)
    assert counting.writes == 1
    assert len(_records(str(tmp_path), "retire")) == trace_lib.FLUSH_RECORDS
    # once the loop is over the tracer writes through, as where no loop laps
    watch.end()
    with trace_lib.span("ckpt"):
        pass
    assert counting.writes == 2 and len(_records(str(tmp_path), "ckpt")) == 1


def test_every_record_is_in_the_file_after_the_exit_hook(tmp_path):
    child = (
        "import sys; sys.path.insert(0, sys.argv[1])\n"
        "from neural_networks_parallel_training_with_mpi_tpu.train import "
        "trace\n"
        "tracer = trace.start_run(sys.argv[2], ledger=False)\n"
        "watch = trace.LapWatch('child')\n"
        "for i in range(40):\n"
        "    watch.lap(i)\n"
        "    with trace.span('dispatch', step=i):\n"
        "        pass\n"
        "sys.exit(0)\n")     # no end(), no close(): the hook alone
    done = subprocess.run([sys.executable, "-c", child, str(REPO),
                           str(tmp_path)], timeout=120,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0
    steps = [r["step"] for r in _records(str(tmp_path), "dispatch")]
    assert steps == list(range(40))
    assert not any(r.get("final") for r in _records(str(tmp_path)))


def test_the_hard_exit_path_writes_what_waits(tmp_path):
    from neural_networks_parallel_training_with_mpi_tpu.train import (
        telemetry,
    )

    trace_lib.start_run(str(tmp_path), ledger=False)
    watch = trace_lib.LapWatch("dying")
    watch.lap(0)
    with trace_lib.span("dispatch", step=0):
        pass
    assert _records(str(tmp_path), "dispatch") == []
    assert telemetry.emergency_dump("crash@0 (injected)") is None  # no
    # telemetry is on, and the spans are in the file all the same
    assert len(_records(str(tmp_path), "dispatch")) == 1


# ---------------------------------------------------------------------------
# the real loops on a toy model
# ---------------------------------------------------------------------------

def _slow_once(fn, when, seconds, span_name):
    """``fn`` with one sleep planted ahead of it, inside a span of that
    name, at the first call for which ``when()`` holds."""
    state = {"done": False}

    def slow(*args, **kw):
        if not state["done"] and when():
            state["done"] = True
            with trace_lib.span(span_name):
                time.sleep(seconds)
        return fn(*args, **kw)

    return slow


def test_a_tick_that_waits_in_land_is_a_stall_of_the_scheduler(tmp_path,
                                                               capfd):
    from neural_networks_parallel_training_with_mpi_tpu.models.transformer import (
        Transformer, TransformerConfig,
    )
    from neural_networks_parallel_training_with_mpi_tpu.serve import (
        Scheduler, ServeConfig,
    )
    from neural_networks_parallel_training_with_mpi_tpu.utils import prng

    # a model no other test file serves: the serving programs are cached a
    # process, and tests/test_trace.py counts its own compiles of them
    model = Transformer(TransformerConfig(
        vocab_size=80, max_seq_len=64, n_layers=2, d_model=32, n_heads=4,
        d_ff=96))
    params = model.init(prng.init_key(0))
    tdir = tmp_path / "run"
    now = [0.0]
    sched = Scheduler(model, params, ServeConfig(
        slots=2, num_blocks=24, block_size=8, prefill_chunk=8,
        telemetry_dir=str(tdir), trace_dir=str(tmp_path / "trace")),
        now_fn=lambda: now[0])
    seen = []
    listener = lambda n, t, d, a: seen.append((n, d, dict(a)))  # noqa: E731
    trace_lib.add_listener(listener)
    try:
        sched.server.land = _slow_once(
            sched.server.land, lambda: sched.tick_no == 30, 0.6, "land")
        rids = [sched.submit([1, 2, 3], 50), sched.submit([4, 5], 50)]
        while sched.in_flight() or sched.pending():
            now[0] += 1.0
            sched.tick()
        assert sched.tick_no > 40 and all(sched.done(r) for r in rids)
        stalls = [s for s in seen if s[0] == "stall"]
        planted = [s for s in stalls if s[2]["n"] == 30]
        assert len(planted) == 1 and len(stalls) == sched._laps.stalls
        _, dur, attrs = planted[0]
        assert attrs["loop"] == "serve_tick" and attrs["where"] == "land"
        assert attrs["cause"] == "waiting" and attrs["t_now"] == 30.0
        assert 0.6 <= attrs["where_s"] <= dur < 1.0
        snap = sched._snapshot()
        assert snap["stalls"] == len(stalls) >= 1
        assert snap["stall_s"] >= attrs["excess_s"] - 1e-5
        capfd.readouterr()
        sched.close()
    finally:
        trace_lib.remove_listener(listener)
    err = capfd.readouterr().err
    lines = [l for l in err.splitlines() if l.startswith("[trace] stall:")]
    assert len(lines) == len(stalls)
    assert sum("serve_tick 30," in l and "in land 0.6" in l
               for l in lines) == 1
    recs = [json.loads(l) for l in open(tdir / "metrics.jsonl")]
    ticks = [r for r in recs if r.get("kind") == "serve"]
    assert ticks[-1]["final"] and ticks[-1]["stalls"] == len(stalls)
    assert ticks[0]["stalls"] == 0
    # the tools render both records
    summary = subprocess.run(
        [sys.executable, str(REPO / "tools" / "metrics_summary.py"),
         str(tdir)], capture_output=True, text=True, timeout=120)
    assert summary.returncode == 0, summary.stderr
    assert f"STALLS: {len(stalls)} ticks ran long" in summary.stdout
    report = subprocess.run(
        [sys.executable, "-S", str(REPO / "tools" / "trace_report.py"),
         str(tmp_path / "trace")], capture_output=True, text=True,
        timeout=120)
    assert report.returncode == 0, report.stderr
    assert "STALL serve_tick 30: 0.6" in report.stdout
    assert "in land 0.6" in report.stdout and "cause waiting" in report.stdout
    assert "\n  stall " not in report.stdout       # not among the phases
    chrome = json.load(open(tmp_path / "trace" / "trace.json"))["traceEvents"]
    stall_tids = {e["tid"] for e in chrome if e.get("name") == "stall"}
    named = {e["tid"] for e in chrome if e.get("name") == "thread_name"
             and e["args"]["name"] == "stalls"}
    assert len(stall_tids) == 1 and stall_tids == named
    assert stall_tids.isdisjoint(
        {e["tid"] for e in chrome if e.get("name") == "decode"})


def test_a_step_that_waits_for_its_batch_is_a_stall_in_load(tmp_path, mesh8,
                                                            capfd):
    from neural_networks_parallel_training_with_mpi_tpu.train.trainer import (
        Trainer,
    )

    cfg = TrainConfig(
        nepochs=8, batch_size=8, full_batch=False, lr=0.005, shuffle=True,
        data=DataConfig(dataset="regression", n_samples=32),
        telemetry_dir=str(tmp_path / "run"), trace=True, metrics_every=1)
    trainer = Trainer(cfg, mesh=mesh8)
    epoch_of = trainer.loader.epoch

    def epoch(e, start_step=0):
        for i, batch in enumerate(epoch_of(e, start_step=start_step)):
            if (e, i) == (6, 1):
                time.sleep(0.7)         # inside the ``load`` span's next()
            yield batch

    trainer.loader.epoch = epoch
    res = trainer.fit()
    assert res["steps"] == 32
    stalls = _records(str(tmp_path / "run" / "trace"), "stall")
    planted = [s for s in stalls if s["where"] == "load"]
    assert len(planted) == 1
    (stall,) = planted
    assert stall["loop"] == "train_step" and stall["n"] == 25
    assert stall["cause"] == "waiting" and 0.7 <= stall["where_s"]
    assert stall["t_now"] is None
    loads = _records(str(tmp_path / "run" / "trace"), "load")
    assert "parent" not in loads[0]
    lines = [l for l in capfd.readouterr().err.splitlines()
             if l.startswith("[trace] stall: train_step 25,")]
    assert len(lines) == 1 and "in load 0.7" in lines[0]
    recs = [json.loads(l) for l in open(tmp_path / "run" / "metrics.jsonl")]
    steps = [r for r in recs if r.get("kind") == "step"]
    assert steps[0]["stalls"] == 0 and steps[-1]["stalls"] == len(stalls)
    assert steps[-1]["stall_s"] >= stall["excess_s"] - 1e-5
    events = [r for r in trainer.telemetry.recorder.records
              if r.get("event") == "stall"]
    assert [e["step"] for e in events if e["where"] == "load"] == [25]
    summary = subprocess.run(
        [sys.executable, str(REPO / "tools" / "metrics_summary.py"),
         str(tmp_path / "run")], capture_output=True, text=True, timeout=120)
    assert summary.returncode == 0, summary.stderr
    assert f"STALLS: {len(stalls)} steps ran long" in summary.stdout
