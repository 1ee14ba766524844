"""Disaggregated prefill/decode handoff ledger (serve/fleet.py +
scheduler role seam, DESIGN.md §11).

Pins, by acceptance criterion — a request killed in EACH handoff state
recovers exactly once, with tokens byte-identical to the undisturbed
single-scheduler reference (greedy decode is deterministic, so any
duplicate or lost execution would show up as a token diff or a counter):

* **steady state**: every request through a 1-prefill + 2-decode pool
  commits exactly one handoff and matches the unified reference
  byte-for-byte; both roles' block allocators drain to zero refcounts.
* **killed BEFORE commit**: prefill dies mid-prefill — the router
  requeues to the surviving prefill replica (one requeue, one commit,
  no redecode) or, with no prefill pool left, serves unified on the
  decode pool (degraded mode, zero commits).
* **killed IN FLIGHT**: the inject target accepts the record at the
  wire and never acks — the ledger timeout aborts, retries with
  backoff, and the record commits ONCE (no re-prefill: the payload
  never left the router).
* **killed AFTER commit**: the decode replica dies mid-decode — the
  ledger still holds the exported blocks + first token, so the sibling
  re-decodes from the record (one redecode, prefill never repaid).

All in-process (the core-lane shape); the subprocess versions — SIGKILL
at the Nth handoff under the group supervisor — live in the chaos
campaign's ``fleet_disagg_handoff`` scenario
(tests/test_chaos.py, slow lane).
"""

import time

import pytest

from neural_networks_parallel_training_with_mpi_tpu.models import (
    Transformer, TransformerConfig,
)
from neural_networks_parallel_training_with_mpi_tpu.serve import (
    FleetRouter, InprocReplica, Scheduler, ServeConfig, make_requests,
)
from neural_networks_parallel_training_with_mpi_tpu.utils import prng

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
    """``jobs`` = [(prompt, max_new)] through ONE unified scheduler —
    the undisturbed greedy reference."""
    sched = _sched(model, params, queue_depth=64, num_blocks=64)
    try:
        rids = [sched.submit(p, m) for p, m in jobs]
        assert all(r is not None for r in rids)
        sched.run_until_drained()
        return [sched.result(r) for r in rids]
    finally:
        sched.close()


def _drive(router, rids, *, sleep=0.0, max_iter=20000):
    """Pump until every rid completes; returns nothing (results are
    read off the router)."""
    done = set()
    for _ in range(max_iter):
        done.update(router.pump())
        if all(r in done for r in rids):
            return
        if sleep:
            time.sleep(sleep)
    raise AssertionError(
        f"requests never drained: {sorted(set(rids) - done)} missing; "
        f"phases={[(r, router.reqs[r].phase) for r in rids]}")


def _drive_until(router, cond, *, max_iter=20000):
    for _ in range(max_iter):
        router.pump()
        if cond():
            return
    raise AssertionError("condition never met while pumping")


def _drained(*handles):
    for h in handles:
        h.sched.server.allocator.assert_drained()


def _close(router, *handles):
    router.close()
    for h in handles:
        h.sched.close()


# ---------------------------------------------------------------------------
# steady state: byte identity + exactly one commit per request
# ---------------------------------------------------------------------------

def test_disagg_tokens_byte_identical_to_unified(lm):
    model, params = lm
    plan = make_requests(4, 2, vocab_size=V, prompt_lens=(4, 20),
                         max_new=(4, 12), seed=5)
    jobs = [(r["prompt"], r["max_new"]) for reqs in plan for r in reqs]
    ref = _reference(model, params, jobs)
    pre = InprocReplica(_sched(model, params, role="prefill",
                               replica=0), name="pre-0")
    d0 = InprocReplica(_sched(model, params, role="decode",
                              replica=1), name="dec-0")
    d1 = InprocReplica(_sched(model, params, role="decode",
                              replica=2), name="dec-1")
    router = FleetRouter([pre, d0, d1], queue_depth=64)
    try:
        rids = [router.submit(p, m) for p, m in jobs]
        assert all(r is not None for r in rids)
        _drive(router, rids)
        for rid, want in zip(rids, ref):
            assert router.result(rid) == want
        # every request took the handoff path exactly once; no
        # recovery machinery fired in steady state
        assert router.handoffs == len(jobs)
        assert router.handoff_retries == 0
        assert router.handoff_reprefills == 0
        assert router.redecodes == 0
        assert router.requeued == 0
        assert router.degraded_dispatches == 0
        # both roles' allocators drained: the prefill released every
        # exported block at commit, the decode pools at retire
        _drained(pre, d0, d1)
    finally:
        _close(router, pre, d0, d1)


# ---------------------------------------------------------------------------
# killed BEFORE commit
# ---------------------------------------------------------------------------

def test_prefill_death_before_commit_requeues_to_sibling_prefill(lm):
    model, params = lm
    prompt, max_new = list(range(1, 25)), 8     # 2 prefill chunks
    [want] = _reference(model, params, [(prompt, max_new)])
    pre0 = InprocReplica(_sched(model, params, role="prefill",
                                replica=0), name="pre-0")
    pre1 = InprocReplica(_sched(model, params, role="prefill",
                                replica=1), name="pre-1")
    dec = InprocReplica(_sched(model, params, role="decode",
                               replica=2), name="dec-0")
    router = FleetRouter([pre0, pre1, dec], queue_depth=16)
    try:
        rid = router.submit(prompt, max_new)
        assert rid is not None
        req = router.reqs[rid]
        _drive_until(router, lambda: req.phase == "prefilling")
        victim = next(h for h in (pre0, pre1) if h.name == req.replica)
        survivor = pre1 if victim is pre0 else pre0
        victim.fail()
        router.on_replica_down(victim.name)
        _drive(router, [rid])
        assert router.result(rid) == want
        # one requeue (the pre-commit death), then the normal path:
        # exactly one commit, no redecode, no re-prefill bookkeeping
        # (the record never existed when the prefill died)
        assert router.requeued == 1
        assert router.handoffs == 1
        assert router.redecodes == 0
        assert router.handoff_reprefills == 0
        assert req.prefill_replica == survivor.name
        _drained(survivor, dec)
    finally:
        _close(router, pre0, pre1, dec)


def test_prefill_pool_death_degrades_to_unified_on_decode(lm):
    model, params = lm
    prompt, max_new = list(range(1, 25)), 8
    [want] = _reference(model, params, [(prompt, max_new)])
    pre = InprocReplica(_sched(model, params, role="prefill",
                               replica=0), name="pre-0")
    dec = InprocReplica(_sched(model, params, role="decode",
                               replica=1), name="dec-0")
    router = FleetRouter([pre, dec], queue_depth=16)
    try:
        rid = router.submit(prompt, max_new)
        assert rid is not None
        req = router.reqs[rid]
        _drive_until(router, lambda: req.phase == "prefilling")
        pre.fail()
        router.on_replica_down(pre.name)
        _drive(router, [rid])
        assert router.result(rid) == want
        # no prefill pool left: the decode replica served END-TO-END
        # (degraded mode) — zero commits, and the degraded dispatch
        # is counted so the autopilot/bench can price it
        assert router.requeued == 1
        assert router.handoffs == 0
        assert router.degraded_dispatches >= 1
        assert router.load_report()["now"]["degraded"] is True
        _drained(dec)
    finally:
        _close(router, pre, dec)


# ---------------------------------------------------------------------------
# killed IN FLIGHT: accepted at the wire, never acked
# ---------------------------------------------------------------------------

class _StallOnceReplica(InprocReplica):
    """Accepts the first inject at the wire and swallows it — no ack,
    no stream, the subprocess wedge the ledger timeout exists for."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.swallowed = 0

    def inject(self, req, payload):
        if not self.swallowed:
            self.swallowed = 1
            return True
        return super().inject(req, payload)


def test_handoff_timeout_aborts_and_retries_exactly_once(lm):
    model, params = lm
    prompt, max_new = list(range(1, 13)), 8
    [want] = _reference(model, params, [(prompt, max_new)])
    pre = InprocReplica(_sched(model, params, role="prefill",
                               replica=0), name="pre-0")
    dec = _StallOnceReplica(_sched(model, params, role="decode",
                                   replica=1), name="dec-0")
    router = FleetRouter([pre, dec], queue_depth=16,
                         handoff_timeout_s=0.05)
    try:
        rid = router.submit(prompt, max_new)
        assert rid is not None
        _drive(router, [rid], sleep=0.002)
        assert router.result(rid) == want
        assert dec.swallowed == 1
        # the timeout re-owned the record and re-dispatched it: one
        # commit, >=1 retry, and the payload never left the router so
        # prefill was NOT repaid
        assert router.handoffs == 1
        assert router.handoff_retries >= 1
        assert router.handoff_reprefills == 0
        assert router.redecodes == 0
        _drained(pre, dec)
    finally:
        _close(router, pre, dec)


# ---------------------------------------------------------------------------
# killed AFTER commit: re-decode from the ledger record
# ---------------------------------------------------------------------------

def test_decode_death_after_commit_redecodes_from_ledger(lm):
    model, params = lm
    prompt, max_new = list(range(1, 13)), 10
    [want] = _reference(model, params, [(prompt, max_new)])
    pre = InprocReplica(_sched(model, params, role="prefill",
                               replica=0), name="pre-0")
    d0 = InprocReplica(_sched(model, params, role="decode",
                              replica=1), name="dec-0")
    d1 = InprocReplica(_sched(model, params, role="decode",
                              replica=2), name="dec-1")
    router = FleetRouter([pre, d0, d1], queue_depth=16)
    try:
        rid = router.submit(prompt, max_new)
        assert rid is not None
        req = router.reqs[rid]
        _drive_until(router, lambda: req.phase == "decoding")
        victim = next(h for h in (d0, d1) if h.name == req.replica)
        sibling = d1 if victim is d0 else d0
        victim.fail()
        router.on_replica_down(victim.name)
        _drive(router, [rid])
        assert router.result(rid) == want
        # the ledger record survived the decode death: ONE redecode on
        # the sibling, the original single commit, and never a
        # re-prefill or a generic requeue (prefill is not repaid)
        assert router.redecodes == 1
        assert router.handoffs == 1
        assert router.handoff_reprefills == 0
        assert router.requeued == 0
        assert req.replica == sibling.name
        _drained(pre, sibling)
    finally:
        _close(router, pre, d0, d1)


def test_decode_pool_death_reprefills_unified_on_prefill_pool(lm):
    """A committed ledger record whose decode DUTY disappears entirely
    (no sibling decode, no unified fallback) must not strand: the
    record drops to a unified requeue — re-prefill on the surviving
    pool, the one recovery that repays prefill — and it is counted."""
    model, params = lm
    prompt, max_new = list(range(1, 13)), 10
    [want] = _reference(model, params, [(prompt, max_new)])
    pre = InprocReplica(_sched(model, params, role="prefill",
                               replica=0), name="pre-0")
    dec = InprocReplica(_sched(model, params, role="decode",
                               replica=1), name="dec-0")
    router = FleetRouter([pre, dec], queue_depth=16)
    try:
        rid = router.submit(prompt, max_new)
        assert rid is not None
        req = router.reqs[rid]
        _drive_until(router, lambda: req.phase == "decoding")
        dec.fail()
        router.on_replica_down(dec.name)
        _drive(router, [rid])
        assert router.result(rid) == want
        # death converted the record to a redecode, the dead pool
        # converted the redecode to a counted re-prefill, and the
        # request finished END-TO-END on the prefill pool (degraded)
        assert router.redecodes == 1
        assert router.handoff_reprefills == 1
        assert router.requeued == 1
        assert router.handoffs == 1
        assert router.degraded_dispatches >= 1
        _drained(pre)
    finally:
        _close(router, pre, dec)


# ---------------------------------------------------------------------------
# the request boundary (ISSUE 36) across the handoff
# ---------------------------------------------------------------------------

def test_single_token_requests_and_rows_in_flight_cross_the_handoff(lm):
    """Requests of one token finish on the prefill role (their row is taken
    at the prefill and reported by that tick's landing: no export of a
    stream that is gone), the others are exported, imported through the
    admission's one program (row and position in one call) and finished on
    the decode role with their rows landing a program behind; every token
    equals the unified scheduler's, and both roles drain."""
    import numpy as np

    model, params = lm
    rng = np.random.default_rng(9)
    jobs = [(rng.integers(0, V, int(p)).tolist(), int(m))
            for p, m in ((5, 1), (17, 6), (3, 1), (9, 2), (12, 9), (20, 3))]
    ref = _reference(model, params, jobs)
    pre = InprocReplica(_sched(model, params, role="prefill", replica=0),
                        name="pre-0")
    dec = InprocReplica(_sched(model, params, role="decode", replica=1),
                        name="dec-0")
    router = FleetRouter([pre, dec], queue_depth=64)
    try:
        rids = [router.submit(p, m) for p, m in jobs]
        assert all(r is not None for r in rids)
        _drive(router, rids)
        for rid, want in zip(rids, ref):
            assert router.result(rid) == want
        assert router.handoffs == sum(1 for _p, m in jobs if m > 1)
        assert pre.sched.handed_off == router.handoffs
        assert pre.sched.server.rows_landed == 2        # the single tokens
        assert dec.sched.server.rows_landed == router.handoffs
        assert not pre.sched.server._in_flight
        assert not dec.sched.server._in_flight
        _drained(pre, dec)
    finally:
        _close(router, pre, dec)
