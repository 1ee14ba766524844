"""A whole run on the CPU at a toy size, through the same functions the chip
runs; the control; and the timed path broken underneath, one fault at a time.

The control and the faults have to come out as not correct.  At this size the
limits are the toys' own (``tests/data/workloads``); the real cells' limits
were set from chip runs (PERF.md).
"""

import json
import subprocess
import sys

import pytest

from conftest import ROOT


def names(res, ok):
    return sorted(c["name"] for c in res["checks"] if c["ok"] == ok)


def test_train_cell_end_to_end(run_cell, bench_dir):
    from benchmark import run as runner

    cell, dev, res = run_cell("tiny-train", trace=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 3 and res["failed"] == 0
    assert res["end_to_end"]["train_tokens_per_s"] > 0
    assert res["end_to_end"]["setup_s"] > 0
    metrics = runner.per_layer_metrics(cell, res, dev, bench_dir)
    # the toy's own metric is found by name; no device metric on a CPU
    assert set(metrics) == {"compile_s", "dispatch_ms.train",
                            "tiny_fetch_ms.train"}


def test_train_cell_on_four_devices(run_cell):
    cell, dev, res = run_cell("tiny-train-dp4")
    assert dev["count"] == 4 and res["correct"], res["checks"]


def test_seed_past_32_bits_gives_the_same_inputs_again(run_cell):
    a = run_cell("tiny-train", seed=2**31 + 5)[2]
    b = run_cell("tiny-train", seed=2**31 + 5)[2]
    c = run_cell("tiny-train", seed=5)[2]
    la, lb, lc = (r["obs"]["readings"]["prog"]["losses"] for r in (a, b, c))
    assert la == lb != lc and a["correct"]


def test_control_int8_training_is_not_correct(run_cell):
    """The program's own int8 matmul path is the nearest precision below the
    configuration's bfloat16."""
    res = run_cell("tiny-train", extra_flags=["--matmul_dtype", "int8"])[2]
    assert not res["correct"]
    assert set(names(res, False)) & {"loss_step1", "grad_norm", "change_norm"}


def _unchanged(trainer):
    step = trainer.train_step

    def broken(state, batch):
        import jax

        keep = jax.tree_util.tree_map(lambda x: x.copy(), state)
        _new, out = step(state, batch)
        return keep, out

    trainer.train_step = broken


def _half_batch(trainer):
    import jax
    import jax.numpy as jnp

    step = trainer.train_step

    def broken(state, batch):
        mask = batch["mask"]
        keep = (jnp.arange(mask.shape[0]) < mask.shape[0] // 2)
        return step(state, {**batch, "mask": jax.device_put(
            mask * keep.astype(mask.dtype), mask.sharding)})

    trainer.train_step = broken


def _no_exchange(trainer):
    from neural_networks_parallel_training_with_mpi_tpu.parallel import (
        data_parallel as dp,
    )

    trainer.train_step = dp.make_train_step(
        trainer.model, trainer.optimizer, trainer.mesh,
        loss_name="cross_entropy", grad_reduction="local")


@pytest.mark.parametrize("cell,tamper", [
    ("tiny-train", _unchanged), ("tiny-train", _half_batch),
    ("tiny-train-dp4", _no_exchange)],
    ids=["state_unchanged", "half_batch", "no_exchange"])
def test_a_broken_step_is_not_correct(run_cell, cell, tamper):
    res = run_cell(cell, tamper=tamper)[2]
    assert not res["correct"]
    assert set(names(res, False)) & {"grad_norm", "change_norm"}


def test_serve_cell_end_to_end(run_cell, bench_dir):
    from benchmark import run as runner

    cell, dev, res = run_cell("tiny-serve", seconds=1.5, trace=True)
    assert res["correct"], res["checks"]
    e2e = res["end_to_end"]
    assert set(e2e) == {"serve_tokens_per_s", "ttft_p90_ms", "itl_p90_ms",
                        "setup_s"}
    assert all(v > 0 for v in e2e.values())
    metrics = runner.per_layer_metrics(cell, res, dev, bench_dir)
    assert set(metrics) == {"compile_s", "prefill_share.serve",
                            "batch_occupancy.serve"}
    assert 0 < metrics["batch_occupancy.serve"]["value"] <= 100


def test_window_tokens_add_up(run_cell):
    """Tokens counted tick by tick from the scheduler's counters are the
    tokens of the answers the harness got back, give or take the requests
    that straddle the window's ends."""
    res = run_cell("tiny-serve", seconds=1.5)[2]
    obs = res["obs"]
    returned = sum(n for _p, n in obs["begun_sizes"])
    assert abs(obs["tokens"] - returned) <= 4 * 12     # 4 callers x longest


def _alter_a_token(sched):
    result = sched.server.result

    def broken(rid):
        toks = result(rid)
        toks[-2] = (toks[-2] + 1) % 256
        return toks

    sched.server.result = broken


@pytest.mark.parametrize("cell", ["tiny-serve", "tiny-switch-serve"])
def test_an_altered_token_is_not_correct(run_cell, cell):
    res = run_cell(cell, seconds=1.0, tamper=_alter_a_token)[2]
    assert not res["correct"] and names(res, False) == ["served_gap_mean_sigma"]


def test_control_fp8_serving_is_not_correct(run_cell):
    """The reference computed in fp8, on the prompts and tokens the program
    served, puts first tokens that lie further below the float32 reference's
    best than the limit allows; what the program served lies within it."""
    import jax

    from benchmark.harness import check
    from benchmark.reference import serve as ref_serve
    from benchmark.reference import control

    cell, _dev, res = run_cell("tiny-serve", seconds=1.0)
    assert res["correct"]
    seqs = [toks for _p, toks in res["obs"]["sample"]]
    plens = [p for p, _toks in res["obs"]["sample"]]
    ref, _ = ref_serve.generated_logits(cell["model"], 7, seqs, plens,
                                        pad_to=16)
    low, _ = ref_serve.generated_logits(cell["model"], 7, seqs, plens,
                                        pad_to=16, quant=control.fp8_cast)
    gaps = check.served_gap(ref, jax.device_get(low.argmax(-1)))
    verdict = check.serve_checks(0, gaps, cell["limits"])
    assert [c["name"] for c in verdict if not c["ok"]] \
        == ["served_gap_mean_sigma"]
    assert gaps.mean() > 3 * res["obs"]["gaps"].mean()


def test_real_cell_without_a_tpu_exits_nonzero():
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "gpt2m-train-b4",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_result_line_has_the_contract_keys(run_cell):
    from benchmark import run as runner

    cell, dev, res = run_cell("tiny-serve", seconds=1.0)
    line = json.loads(json.dumps(runner.result_line(cell, res, dev, False)))
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
