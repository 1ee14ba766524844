"""The chaos campaign (utils/chaos.py) gates on its own invariants.

``lite`` is the plan CI's ``chaos-lite`` job runs under ``python -S``:
supervised stdlib children, a crash beside the same failure announced,
a prefill worker killed mid-handoff and a router killed over its WAL.
Run twice, every invariant must hold, the wall-clock-free digest must be
the same in both passes, and the announced failure must cost no rollback
and no relaunch gap.  ``full`` adds the subprocess-fleet scenarios and is
slow.
"""

import pytest

from neural_networks_parallel_training_with_mpi_tpu.utils import chaos

pytestmark = pytest.mark.chaos


def _categories(doc, name):
    (row,) = [r for r in doc["scenarios"] if r["name"] == name]
    return row["metrics"]["categories"]


def test_lite_campaign_holds_invariants_and_is_reproducible():
    doc = chaos.run_campaign(chaos.load_plan("lite"), repeat=2)
    assert doc["invariants_ok"], doc["problems"]
    assert doc["determinism"]["reproducible"], doc["determinism"]
    crash = _categories(doc, "stub_crash")
    notice = _categories(doc, "stub_preempt")
    assert crash["relaunch_gap"] > 0.0 and crash["rollback"] > 0.0
    assert notice.get("relaunch_gap", 0.0) == 0.0
    assert notice.get("rollback", 0.0) == 0.0
    assert notice["drain"] > 0.0


@pytest.mark.slow
def test_full_campaign_holds_invariants():
    doc = chaos.run_campaign(chaos.load_plan("full"), repeat=1)
    assert doc["invariants_ok"], doc["problems"]
    by = {r["name"]: r for r in doc["scenarios"]}
    assert by["fleet_preempt_notice"]["metrics"]["requeued"] == 0
    assert by["fleet_slow_evict"]["invariants"]["p99_itl_recovered"]
