"""Durable control plane (serve/wal.py + router recovery, DESIGN.md
§11).

Pins, by acceptance criterion:

* **WAL durability grammar**: append/replay roundtrip across segment
  rotation (sealed segments manifest-verified), a torn tail truncated
  at the last valid record (never fatal), a mid-file checksum-corrupt
  record quarantined WITH provenance while later records still replay,
  and a corrupt sealed segment quarantined with its intact lines
  salvaged.
* **Replay exactly-once per phase**: a router relaunched on the same
  WAL dir re-admits unfinished requests in their recorded phase —
  completed ones answer from the journal (never re-executed), queued
  ones re-run, committed handoffs re-inject without repaying prefill
  or convert to a unified reprefill when the decode pool never came
  back — and every token matches the undisturbed reference.
* **Idempotency dedupe**: a resubmit carrying the same client key maps
  to the SAME rid (no second execution), in one life and across lives.
* **Allocator drain**: ``Scheduler.quiesce`` — the one call shared by
  every worker shutdown path, including the orphaned worker whose
  control plane died — evicts everything and proves the allocator
  empty.

All in-process (the core-lane shape); the subprocess versions — a
SIGKILL'd driver process, orphan drain via stdin EOF, whole-process-
group kill — live in the chaos campaign's ``stub_router_kill`` /
``fleet_ctrlplane`` scenarios (tests/test_chaos.py).
"""

import json
import os

import pytest

from neural_networks_parallel_training_with_mpi_tpu.models import (
    Transformer, TransformerConfig,
)
from neural_networks_parallel_training_with_mpi_tpu.serve import (
    FleetRouter, InprocReplica, Scheduler, ServeConfig, make_requests,
)
from neural_networks_parallel_training_with_mpi_tpu.serve import wal
from neural_networks_parallel_training_with_mpi_tpu.utils import (
    ckpt_manifest, prng,
)
from neural_networks_parallel_training_with_mpi_tpu.utils.faults import (
    DRIVER_KINDS, KINDS, FaultPlan,
)
from neural_networks_parallel_training_with_mpi_tpu.utils import goodput

pytestmark = pytest.mark.fleet

V = 64


@pytest.fixture(scope="module")
def lm():
    model = Transformer(TransformerConfig(
        vocab_size=V, max_seq_len=64, n_layers=2, d_model=32,
        n_heads=4, d_ff=64))
    return model, model.init(prng.init_key(0))


def _sched(model, params, *, role="unified", slots=4, queue_depth=16,
           replica=None, num_blocks=None, **kw):
    return Scheduler(model, params, ServeConfig(
        slots=slots, num_blocks=num_blocks or (1 + slots * 4),
        block_size=16, prefill_chunk=16, queue_depth=queue_depth,
        replica=replica, role=role, **kw))


def _reference(model, params, jobs):
    sched = _sched(model, params, queue_depth=64, num_blocks=64)
    try:
        rids = [sched.submit(p, m) for p, m in jobs]
        assert all(r is not None for r in rids)
        sched.run_until_drained()
        return [sched.result(r) for r in rids]
    finally:
        sched.close()


def _drive(router, rids, *, max_iter=20000):
    done = set()
    for _ in range(max_iter):
        done.update(router.pump())
        if all(r in done for r in rids):
            return
    raise AssertionError(
        f"requests never drained: {sorted(set(rids) - done)} missing; "
        f"phases={[(r, router.reqs[r].phase) for r in rids]}")


def _drive_until(router, cond, *, max_iter=20000):
    for _ in range(max_iter):
        router.pump()
        if cond():
            return
    raise AssertionError("condition never met while pumping")


# ---------------------------------------------------------------------------
# WAL grammar: roundtrip, rotation, torn tail, quarantine
# ---------------------------------------------------------------------------

def test_wal_roundtrip_and_rotation(tmp_path):
    root = str(tmp_path / "wal")
    w = wal.WriteAheadLog(root, segment_records=4)
    assert w.open() == []
    for i in range(10):
        w.append("accept", rid=i, idem=f"k{i}")
    w.close()
    # 10 appends at 4/segment: two sealed segments + two active lines
    segs = [p for _, p in wal._segments(root)]
    assert len(segs) == 2
    for seg in segs:
        assert ckpt_manifest.verify(seg) == []  # committed, verifiable
    recs, report = wal.replay(root)
    assert [r["rid"] for r in recs] == list(range(10))
    assert [r["seq"] for r in recs] == list(range(10))
    assert report["records"] == 10
    assert report["quarantined_records"] == 0
    # reopen continues the seq chain past everything replayed
    w2 = wal.WriteAheadLog(root, segment_records=4)
    w2.open()
    assert w2.append("complete", rid=0)["seq"] == 10
    w2.close()


def test_wal_torn_tail_truncated_not_fatal(tmp_path):
    root = str(tmp_path / "wal")
    w = wal.WriteAheadLog(root)
    w.open()
    for i in range(3):
        w.append("accept", rid=i)
    w.close()
    active = os.path.join(root, wal.ACTIVE)
    good_size = os.path.getsize(active)
    with open(active, "a") as f:
        f.write(wal.encode_record({"seq": 3, "kind": "accept",
                                   "rid": 3})[:11])  # no newline
    # read-only replay reports but does NOT repair (live-wal safe)
    recs, report = wal.replay(root, repair=False)
    assert len(recs) == 3 and report["torn_tail_bytes"] > 0
    assert not report["torn_tail_truncated"]
    assert os.path.getsize(active) > good_size
    # open() truncates at the last valid record
    w2 = wal.WriteAheadLog(root)
    recs2 = w2.open()
    assert [r["rid"] for r in recs2] == [0, 1, 2]
    assert w2.report["torn_tail_truncated"]
    assert os.path.getsize(active) == good_size
    # and the log appends on as if the torn write never happened
    w2.append("accept", rid=3)
    w2.close()
    recs3, _ = wal.replay(root)
    assert [r["rid"] for r in recs3] == [0, 1, 2, 3]


def test_wal_midfile_corruption_quarantined(tmp_path):
    root = str(tmp_path / "wal")
    w = wal.WriteAheadLog(root)
    w.open()
    for i in range(4):
        w.append("accept", rid=i)
    w.close()
    active = os.path.join(root, wal.ACTIVE)
    with open(active) as f:
        lines = f.readlines()
    lines[1] = "0" * 16 + lines[1][16:]  # checksum no longer matches
    with open(active, "w") as f:
        f.writelines(lines)
    w2 = wal.WriteAheadLog(root)
    recs = w2.open()
    # the corrupt record is gone; the ones AFTER it still replay (a
    # mid-file bad line is bit rot, not a torn tail)
    assert [r["rid"] for r in recs] == [0, 2, 3]
    assert w2.report["quarantined_records"] == 1
    assert not w2.report["torn_tail_truncated"]
    w2.close()
    qpath = os.path.join(root, wal.QUARANTINE_FILE)
    with open(qpath) as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    assert len(rows) == 1 and rows[0]["origin"] == wal.ACTIVE


def test_wal_corrupt_segment_quarantined_and_salvaged(tmp_path):
    root = str(tmp_path / "wal")
    w = wal.WriteAheadLog(root, segment_records=4)
    w.open()
    for i in range(8):
        w.append("accept", rid=i)
    w.close()
    seg0 = os.path.join(root, f"{wal.SEG_PREFIX}0")
    rec_path = os.path.join(seg0, "records.jsonl")
    with open(rec_path) as f:
        lines = f.readlines()
    lines[2] = "f" * 16 + lines[2][16:]
    with open(rec_path, "w") as f:
        f.writelines(lines)
    assert ckpt_manifest.verify(seg0) != []  # sha mismatch detected
    w2 = wal.WriteAheadLog(root, segment_records=4)
    recs = w2.open()
    assert w2.report["quarantined_segments"] == 1
    assert w2.report["quarantined_records"] == 1
    # the failed segment moved aside; its intact lines were salvaged
    assert not os.path.isdir(seg0)
    assert os.path.isdir(os.path.join(root, f"corrupt-{wal.SEG_PREFIX}0"))
    assert [r["rid"] for r in recs] == [0, 1, 3, 4, 5, 6, 7]
    w2.close()


# ---------------------------------------------------------------------------
# router replay: exactly-once per journaled phase
# ---------------------------------------------------------------------------

def _jobs(n=4):
    plan = make_requests(n, 1, vocab_size=V, prompt_lens=(4, 20),
                         max_new=(4, 10), seed=7)
    return [(r["prompt"], r["max_new"]) for reqs in plan for r in reqs]


def _disagg_pair(model, params, *, tag=""):
    pre = InprocReplica(_sched(model, params, role="prefill",
                               replica=0), name=f"pre{tag}")
    dec = InprocReplica(_sched(model, params, role="decode",
                               replica=1), name=f"dec{tag}")
    return pre, dec


def test_replay_exactly_once_across_restart(lm, tmp_path):
    model, params = lm
    jobs = _jobs(4)
    ref = _reference(model, params, jobs)
    walroot = str(tmp_path / "wal")

    # life 1: crash (stop pumping) after at least one completion, with
    # the rest accepted — a mixed-phase journal
    pre, dec = _disagg_pair(model, params, tag="-l1")
    r1 = FleetRouter([pre, dec], queue_depth=64, wal_dir=walroot)
    rids1 = [r1.submit(p, m, idem=f"k{i}")
             for i, (p, m) in enumerate(jobs)]
    assert all(r is not None for r in rids1)
    _drive_until(r1, lambda: r1.completed >= 1)
    done_life1 = r1.completed
    assert 1 <= done_life1 < len(jobs)
    r1._wal.close()  # the crash: no graceful close, records are fsynced
    pre.sched.close()
    dec.sched.close()

    # life 2: fresh replicas, same journal
    pre2, dec2 = _disagg_pair(model, params, tag="-l2")
    r2 = FleetRouter([pre2, dec2], queue_depth=64, wal_dir=walroot)
    try:
        assert r2.recovery["recovered"]
        assert r2.completed == done_life1       # restored, not re-run
        assert r2.recovery["replayed"] == len(jobs) - done_life1
        assert r2.recovery["lost"] == 0
        # clients resubmit EVERYTHING with the same idempotency keys:
        # every submit maps onto the journal-owned rid, none re-executes
        rids2 = [r2.submit(p, m, idem=f"k{i}")
                 for i, (p, m) in enumerate(jobs)]
        assert rids2 == rids1
        assert r2.recovery["deduped"] == len(jobs)
        _drive(r2, rids1)
        for rid, want in zip(rids1, ref):
            assert r2.result(rid) == want       # byte-identical tokens
        assert r2.completed == len(jobs)        # exactly once, fleetwide
        # allocator drain after recovery: nothing leaked across lives
        pre2.sched.server.allocator.assert_drained()
        dec2.sched.server.allocator.assert_drained()
        assert r2.load_report()["now"]["post_recovery"]
    finally:
        r2.close()
        pre2.sched.close()
        dec2.sched.close()


def test_replay_committed_handoff_converts_without_decode_pool(
        lm, tmp_path):
    model, params = lm
    jobs = _jobs(3)
    ref = _reference(model, params, jobs)
    walroot = str(tmp_path / "wal")

    # life 1: crash right after the first handoff commits
    pre, dec = _disagg_pair(model, params, tag="-c1")
    r1 = FleetRouter([pre, dec], queue_depth=64, wal_dir=walroot)
    rids = [r1.submit(p, m, idem=f"k{i}")
            for i, (p, m) in enumerate(jobs)]
    _drive_until(r1, lambda: r1.handoffs >= 1)
    r1._wal.close()
    pre.sched.close()
    dec.sched.close()

    # life 2: the decode pool never comes back — a prefill-only fleet.
    # The journaled handoff record cannot re-inject; the recovery
    # table's last row converts it to a unified reprefill.
    pre2 = InprocReplica(_sched(model, params, role="prefill",
                                replica=0), name="pre-c2")
    r2 = FleetRouter([pre2], queue_depth=64, wal_dir=walroot)
    try:
        assert r2.recovery["recovered"]
        rids2 = [r2.submit(p, m, idem=f"k{i}")
                 for i, (p, m) in enumerate(jobs)]
        assert rids2 == rids
        _drive(r2, rids)
        assert r2.recovery["converted"] >= 1
        assert r2.handoff_stats()["recovery"]["converted"] >= 1
        for rid, want in zip(rids, ref):
            assert r2.result(rid) == want
        pre2.sched.server.allocator.assert_drained()
    finally:
        r2.close()
        pre2.sched.close()


def test_idempotency_dedupe_same_life(lm, tmp_path):
    model, params = lm
    (prompt, max_new), = _jobs(1)
    rep = InprocReplica(_sched(model, params), name="u0")
    router = FleetRouter([rep], queue_depth=8,
                         wal_dir=str(tmp_path / "wal"))
    try:
        rid = router.submit(prompt, max_new, idem="dup-key")
        _drive(router, [rid])
        assert router.submit(prompt, max_new, idem="dup-key") == rid
        assert router.recovery["deduped"] == 1
        assert router.completed == 1            # no second execution
        # the dedupe re-announces completion so a re-attached client
        # hears about its request again
        assert rid in router.pump()
    finally:
        router.close()
        rep.sched.close()


# ---------------------------------------------------------------------------
# quiesce: the shared worker-shutdown drain
# ---------------------------------------------------------------------------

def test_scheduler_quiesce_drains_allocator(lm):
    model, params = lm
    sched = _sched(model, params)
    try:
        rid = sched.submit([1, 2, 3, 4], 6)
        assert rid is not None
        for _ in range(3):
            sched.tick()                        # mid-flight state
        descs = sched.quiesce()
        assert any(d.get("rid") == rid for d in descs)
        sched.server.allocator.assert_drained()  # quiesce proved it
    finally:
        sched.close()


# ---------------------------------------------------------------------------
# fault kinds + goodput category
# ---------------------------------------------------------------------------

def test_driver_fault_kinds_parse_and_noop_in_apply():
    assert "router_kill" in KINDS and "fleet_kill" in KINDS
    assert DRIVER_KINDS == ("router_kill", "fleet_kill")
    plan = FaultPlan.parse("router_kill@3?max=1,fleet_kill@5?max=1")
    # apply() never fires driver kinds: the victim cannot kill itself
    batch = {"x": [1, 2]}
    assert plan.apply(3, batch) is batch
    assert plan.apply(5, batch) is batch
    # the parent's due-check is the firing path, and max=1 bounds it
    assert plan.fire_if_due("router_kill", 3)
    assert not plan.fire_if_due("router_kill", 3)
    assert not plan.fire_if_due("fleet_kill", 4)
    assert plan.fire_if_due("fleet_kill", 5)


def test_goodput_recovery_category():
    assert "recovery" in goodput.CATEGORIES
    assert goodput.categorize("recovery") == "recovery"
    # recovery outranks the steady-state categories in overlap
    # resolution: a recovery window is never mispriced as step/idle
    assert (goodput.PRIORITY.index("recovery")
            < goodput.PRIORITY.index("step"))
    spans = [{"name": "recovery", "t": 0.0, "dur": 1.0},
             {"name": "dispatch", "t": 1.0, "dur": 1.0, "step": 0}]
    cats, _ = goodput._resolve_retrain(spans)
    secs = goodput._sweep(spans, cats, 0.0, 2.0)
    assert secs["recovery"] == pytest.approx(1.0)
    assert secs["step"] == pytest.approx(1.0)
