"""Test harness: 8 virtual CPU devices, no TPU.

The SPMD logic is tested against fake CPU devices
(``--xla_force_host_platform_device_count=8``) exactly as SURVEY.md §4
prescribes — this plays the role ``mpiexec -n N`` plays for the reference on
a laptop (reference README.md:10-12).

We (a) point XLA_FLAGS at 8 host devices and (b) pin the platform to cpu —
the env var for child processes, the config for this one — *before* any JAX
backend initialization, so no test process ever claims an accelerator.  The
chip is reached only through ``chip_smoke.py`` (one process per chip).
"""

import os

_N_DEVICES = 8

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={_N_DEVICES}"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# Core-lane wall-clock budget (VERDICT r4 item 8: the lane doubled from ~5
# to ~10 min in one round with no brake).  Every `-m "not slow"` session
# appends its duration to .lane_times.jsonl.  A single over-budget run only
# WARNS (ADVICE r5: a green run on a temporarily slow/shared machine must
# not exit 1 on elapsed time alone); the run FAILS only when it also blows
# the machine's own rolling median by a wide margin — i.e. the lane itself
# grew, not the host slowed down.  Heavyweight additions belong in the full
# lane (@slow).
CORE_LANE_BUDGET_S = 600.0
# fail threshold: max(budget, this factor x median of recent recorded runs)
CORE_LANE_MEDIAN_FACTOR = 1.4
_LANE_TIMES = os.path.join(os.path.dirname(__file__), "..",
                           ".lane_times.jsonl")
_session_t0 = None


def pytest_sessionstart(session):
    global _session_t0
    import time as _time

    _session_t0 = _time.time()


def _lane_median(n_recent: int = 10):
    """Median duration of the last ``n_recent`` recorded UNDER-BUDGET full
    core-lane runs (None when there is no usable history).  Two filters
    keep the baseline honest: subset runs (tests <= 100) must not drag it
    down, and over-budget runs must not ratchet it up — otherwise steady
    lane growth would raise its own fail threshold forever and the brake
    (VERDICT r4 item 8) would never engage.  The baseline therefore
    freezes at this machine's last healthy level: growth is bounded at
    CORE_LANE_MEDIAN_FACTOR x that."""
    import json as _json
    import statistics as _stats

    try:
        with open(_LANE_TIMES) as f:
            secs = [r["seconds"] for r in map(_json.loads, f)
                    if isinstance(r.get("seconds"), (int, float))
                    and r.get("tests", 0) > 100
                    and not r.get("over_budget")]
    except (OSError, ValueError):
        return None
    return _stats.median(secs[-n_recent:]) if secs else None


def _lane_rate_median(n_recent: int = 10):
    """Median seconds-PER-TEST over the last ``n_recent`` full core-lane
    runs of ANY status (None without history).  Complements
    :func:`_lane_median`: the absolute median freezes at the last healthy
    level (so growth cannot ratchet it), but on this shared single-core
    host the per-test rate swings 1.2-2.2x with ambient load on IDENTICAL
    code (.lane_times.jsonl r7: half the day's runs were over-budget
    before any lane change) — a run in a loaded window would blow the
    absolute threshold with zero lane growth, the exact "green run on a
    temporarily slow machine" ADVICE r5 says must not exit 1.  Including
    over-budget runs here is deliberate: load moves the rate, lane SIZE
    does not, so this baseline adapts to the machine while staying
    size-independent.  Runs under 60s are aborted/degenerate sessions,
    not rate evidence."""
    import json as _json
    import statistics as _stats

    try:
        with open(_LANE_TIMES) as f:
            rates = [r["seconds"] / r["tests"] for r in map(_json.loads, f)
                     if isinstance(r.get("seconds"), (int, float))
                     and r.get("tests", 0) > 100
                     and r["seconds"] >= 60.0]
    except (OSError, ValueError):
        return None
    return _stats.median(rates[-n_recent:]) if rates else None


def pytest_sessionfinish(session, exitstatus):
    import json as _json
    import time as _time

    if _session_t0 is None:
        return
    marker = session.config.getoption("-m", default="") or ""
    if "not slow" not in marker:
        return  # full lane / targeted runs are unbudgeted
    elapsed = _time.time() - _session_t0
    n = session.testscollected
    median = _lane_median()
    # headroom over THIS machine's recent history; without history the
    # budget alone can only warn (a slow machine's first run must not fail)
    fail_at = (max(CORE_LANE_BUDGET_S, CORE_LANE_MEDIAN_FACTOR * median)
               if median is not None else None)
    rec = {"t_iso": _time.strftime("%Y-%m-%dT%H:%M:%SZ", _time.gmtime()),
           "seconds": round(elapsed, 1), "tests": n,
           "budget_s": CORE_LANE_BUDGET_S,
           "median_s": round(median, 1) if median is not None else None,
           "over_budget": elapsed > CORE_LANE_BUDGET_S}
    try:
        with open(_LANE_TIMES, "a") as f:
            f.write(_json.dumps(rec) + "\n")
    except OSError:
        pass
    if elapsed > CORE_LANE_BUDGET_S and n > 100:
        # n > 100 guards against budget-failing a filtered subset run
        # that happens to pass -m "not slow"
        rate_median = _lane_rate_median()
        # the HARD fail needs evidence the LANE grew, not just that this
        # window's host load was high: the absolute threshold (frozen
        # healthy-median x factor) AND the size-independent per-test
        # rate vs this machine's load-inclusive recent rate.  A loaded
        # window inflates both elapsed and the rate of the UNCHANGED
        # lane identically, so the rate ratio stays ~1 and the run warns
        # instead of failing (ADVICE r5); a genuinely heavier lane
        # raises the rate above its own recent history and still fails.
        rate_grew = (rate_median is None
                     or elapsed / n > CORE_LANE_MEDIAN_FACTOR * rate_median)
        # the rate gate is size-independent, so growth by ADDING
        # average-cost tests could otherwise warn forever — the hard
        # ceiling (2x budget) is the wall-clock bound no load excuse
        # waives
        if elapsed > 2 * CORE_LANE_BUDGET_S:
            rate_grew = True
        if fail_at is not None and elapsed > fail_at and rate_grew:
            session.exitstatus = 1
            print(f"\nCORE LANE OVER BUDGET: {elapsed:.0f}s > "
                  f"{CORE_LANE_BUDGET_S:.0f}s budget AND > "
                  f"{fail_at:.0f}s ({CORE_LANE_MEDIAN_FACTOR}x this "
                  f"machine's {median:.0f}s rolling median), with the "
                  f"per-test rate ({elapsed / n:.2f}s) above "
                  f"{CORE_LANE_MEDIAN_FACTOR}x its recent median — the "
                  "lane grew; move the heaviest new tests to the full "
                  "lane (@pytest.mark.slow)", flush=True)
        elif fail_at is not None and elapsed > fail_at:
            print(f"\nWARNING: core lane over budget ({elapsed:.0f}s > "
                  f"{fail_at:.0f}s fail threshold) but the per-test rate "
                  f"({elapsed / n:.2f}s/test) is within "
                  f"{CORE_LANE_MEDIAN_FACTOR}x this machine's recent "
                  f"rate median ({rate_median:.2f}s/test) — host load, "
                  "not lane growth; not failing the run", flush=True)
        elif median is not None:
            print(f"\nWARNING: core lane over budget ({elapsed:.0f}s > "
                  f"{CORE_LANE_BUDGET_S:.0f}s) but within this machine's "
                  f"rolling-median headroom (median {median:.0f}s) — not "
                  "failing the run", flush=True)
        else:
            print(f"\nWARNING: core lane over budget ({elapsed:.0f}s > "
                  f"{CORE_LANE_BUDGET_S:.0f}s); no .lane_times.jsonl "
                  "history yet — not failing the run", flush=True)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices("cpu")
    assert len(devs) >= _N_DEVICES, (
        f"expected {_N_DEVICES} virtual CPU devices, got {len(devs)}"
    )
    return devs[:_N_DEVICES]


@pytest.fixture(scope="session")
def mesh8(devices):
    from neural_networks_parallel_training_with_mpi_tpu.parallel.mesh import (
        make_mesh,
    )
    from neural_networks_parallel_training_with_mpi_tpu.config import MeshConfig

    return make_mesh(MeshConfig(data=8), devices=devices)


@pytest.fixture(scope="session")
def mesh1(devices):
    from neural_networks_parallel_training_with_mpi_tpu.parallel.mesh import (
        make_mesh,
    )
    from neural_networks_parallel_training_with_mpi_tpu.config import MeshConfig

    return make_mesh(MeshConfig(data=1), devices=devices[:1])


@pytest.fixture
def staggered_batch():
    """``run(net, params, requests, callers, **serve_config)``: a closed loop
    of ``callers`` callers over one ``Scheduler`` (each sends its next
    ``(prompt, max_new)`` of ``requests``, ``max_new`` 2 or more, when its
    last was reported), so
    streams finish at different ticks while others run, their rows land a
    program behind, and their slots are admitted again with a row in flight.
    Checks, for every request: it is reported in the tick after its last
    step while another stream runs (in that same tick when none does), and
    its tokens are those the same request gets alone on a fresh scheduler
    (one stream: the row lands in the tick of its last step, with nothing
    behind it); ``rows_landed_behind`` counts every row but those.  Returns
    the scheduler, closed, for the caller's own counters."""
    from neural_networks_parallel_training_with_mpi_tpu.serve import (
        Scheduler, ServeConfig,
    )

    def alone(net, params, prompt, n, cfg):
        sched = Scheduler(net, params, ServeConfig(**cfg))
        rid = sched.submit(prompt, n)
        sched.run_until_drained()
        toks = sched.result(rid)
        assert (sched.server.rows_landed,
                sched.server.rows_landed_behind) == (1, 0)
        sched.close()
        return toks

    def run(net, params, requests, callers, **cfg):
        sched = Scheduler(net, params, ServeConfig(**cfg))
        srv = sched.server
        todo = list(requests)
        waiting, sent, served = [None] * callers, {}, {}
        taken_at = {}       # rid -> tick whose step made its last token
        unqueued = 0        # rows landed with nothing queued behind them
        for _ in range(10_000):
            for c in range(callers):
                if waiting[c] is None and todo:
                    prompt, n = todo.pop(0)
                    waiting[c] = sched.submit(prompt, n)
                    sent[waiting[c]] = (prompt, n)
            if not any(w is not None for w in waiting):
                break
            reported = sched.tick()
            running = [r for r, s in sched._srv_rid.items() if srv.holds(s)]
            for rid, s in sched._srv_rid.items():
                if not srv.holds(s):        # taken, still in flight
                    taken_at.setdefault(rid, sched.tick_no)
            for rid in reported:
                if rid in taken_at:
                    assert sched.tick_no == taken_at.pop(rid) + 1, rid
                else:   # taken and landed in this tick: nothing runs on
                    assert not running, (rid, running)
                    unqueued += 1
                served[rid] = sched.result(rid)
                waiting[waiting.index(rid)] = None
                assert sched.stats(rid).t_done is not None
        assert not todo and not taken_at and len(served) == len(requests)
        assert not srv._in_flight
        assert (srv.rows_landed, srv.rows_landed_behind) == (
            len(requests), len(requests) - unqueued)
        srv.assert_drained()
        sched.close()
        for rid, (prompt, n) in sent.items():
            assert served[rid] == alone(net, params, prompt, n, cfg), rid
        return sched

    return run
