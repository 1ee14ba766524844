"""The scope and idle metrics through a whole toy run: cells that list them
are added as files (``tests/data/workloads/tiny-*-named.json``), and the
harness finds each metric's file and reducer by name.

The CPU's own trace has no device plane, so there the new metrics are left
out and nothing raises: that is the path a program without scopes takes on
the chip too (the parent of the PR that brought them).  With a small
hand-written trace in the profiler's place, the same run reports them.
"""

import pytest

import xspace_writer

MS = 1_000_000


def _plant(res, planes, steps=None):
    """A hand-written trace where the run's own trace lies, read afresh."""
    obs = res["obs"]
    xspace_writer.write(
        obs["profiler"].dir / "plugins/profile/zz_planted/t.xplane.pb", planes)
    for cached in ("_trace", "_scopes", "_host_phases"):
        obs.pop(cached, None)
    if steps is not None:
        obs["traced_steps"] = steps


def test_train_cell_reports_scope_and_idle_metrics(run_cell, bench_dir):
    from benchmark import run as runner

    cell, dev, res = run_cell("tiny-train-named", trace=True)
    assert res["correct"], res["checks"]
    metrics = runner.per_layer_metrics(cell, res, dev, bench_dir)
    assert set(metrics) == {"compile_s", "dispatch_ms.train"}   # a CPU trace
    step = "jit(shard_step)/shard_map/"
    fwd = step + "loss_and_grad/jvp(attention)/attn_dense/dot_general:"
    bwd = step + "loss_and_grad/transpose(jvp(ffn))/dot_general:"
    _plant(res, [
        xspace_writer.plane("/device:TPU:0", {
            "XLA Ops": [(f"%fusion.{i} = f32[8] fusion(%p)", s * MS, d * MS,
                         {"tf_op": p})
                        for i, (p, s, d) in enumerate([
                            (fwd, 0, 10), (bwd, 10, 20),
                            (step + "optimizer_update/mul:", 30, 4),
                            (fwd, 40, 10), (bwd, 50, 20)])],
            "XLA Modules": [("jit_shard_step(1)", 0, 34 * MS, {}),
                            ("jit_shard_step(1)", 40 * MS, 30 * MS, {})]}),
        xspace_writer.plane("/host:CPU", {"python3": [
            ("nnpt:fetch", 1 * MS, 34 * MS, {}),
            ("nnpt:train_step", 36 * MS, 6 * MS, {}),
            ("nnpt:dispatch", 37 * MS, 5 * MS, {}),
            ("nnpt:train_step", 80 * MS, 2 * MS, {}),
            ("nnpt:dispatch", 80 * MS, 2 * MS, {})]})], steps=2)
    metrics = runner.per_layer_metrics(cell, res, dev, bench_dir)
    value = {k: v["value"] for k, v in metrics.items()}
    assert value["fwd_ms.train"] == pytest.approx(10.0)
    assert value["bwd_ms.train"] == pytest.approx(20.0)
    assert value["attention_ms.train"] == pytest.approx(10.0)
    assert value["optimizer_ms.train"] == pytest.approx(2.0)
    # the one gap, 34..40 ms: 1 under fetch, 1 under none, 1 under
    # train_step alone, 3 under dispatch; over two dispatches
    assert value["idle_in_dispatch_ms.train"] == pytest.approx(1.5)
    assert value["idle_in_fetch_ms.train"] == pytest.approx(0.5)
    assert value["idle_unnamed_ms.train"] == pytest.approx(0.5)
    assert all(m["unit"] == "ms" for k, m in metrics.items()
               if k != "compile_s")


def test_serve_cell_reports_scope_and_idle_metrics(run_cell, bench_dir):
    from benchmark import run as runner

    cell, dev, res = run_cell("tiny-serve-named", seconds=1.5, trace=True)
    assert res["correct"], res["checks"]
    metrics = runner.per_layer_metrics(cell, res, dev, bench_dir)
    assert set(metrics) == {"compile_s", "prefill_share.serve"}
    gather = "jit(step)/attention/paged_gather/gather:"
    core = "jit(step)/attention/attn_core/bcgqk,bkcd->bqcgd/dot_general:"
    _plant(res, [
        xspace_writer.plane("/device:TPU:0", {
            "XLA Ops": [("%fusion.1 = bf16[8] fusion(%p)", 0, 6 * MS,
                         {"tf_op": gather}),
                        ("%fusion.2 = bf16[8] fusion(%p)", 6 * MS, 2 * MS,
                         {"tf_op": core}),
                        ("%fusion.9 = bf16[8] fusion(%p)", 10 * MS, 5 * MS,
                         {"tf_op": "jit(prefill)/attention/paged_gather/g:"}),
                        ("%fusion.1 = bf16[8] fusion(%p)", 20 * MS, 6 * MS,
                         {"tf_op": gather})],
            "XLA Modules": [("jit_step(1)", 0, 8 * MS, {}),
                            ("jit_prefill(2)", 10 * MS, 5 * MS, {}),
                            ("jit_step(1)", 20 * MS, 6 * MS, {})]}),
        xspace_writer.plane("/host:CPU", {"python3": [
            ("nnpt:decode", 0, 9 * MS, {}),
            ("nnpt:decode/finish", 8 * MS, 1 * MS, {}),
            ("nnpt:prefill", 12 * MS, 2 * MS, {}),
            ("nnpt:decode", 16 * MS, 10 * MS, {})]})])
    metrics = runner.per_layer_metrics(cell, res, dev, bench_dir)
    value = {k: v["value"] for k, v in metrics.items()}
    assert value["decode_gather_ms.serve"] == pytest.approx(6.0)
    assert value["decode_attn_core_ms.serve"] == pytest.approx(1.0)
    # gaps 8..10 and 15..20 ms: 1 + 4 under decode, 2 under none, per two
    # decode spans; the prefill span is there and no idle falls under it
    assert value["idle_in_decode_ms.serve"] == pytest.approx(2.5)
    assert value["idle_unnamed_ms.serve"] == pytest.approx(1.0)
    assert value["idle_in_prefill_ms.serve"] == 0.0


TRAIN_ROWS = {"fwd_ms.train", "bwd_ms.train", "optimizer_ms.train",
              "attention_ms.train", "ce_ms.train", "grad_exchange_ms.train",
              "idle_in_dispatch_ms.train", "idle_in_fetch_ms.train",
              "idle_unnamed_ms.train"}
SERVE_ROWS = {"decode_gather_ms.serve", "decode_scatter_ms.serve",
              "decode_attn_core_ms.serve", "idle_in_prefill_ms.serve",
              "idle_in_decode_ms.serve", "idle_unnamed_ms.serve"}


@pytest.mark.parametrize("name, rows", [
    ("gpt2m-train-b4", TRAIN_ROWS), ("gpt2m-train-dp4", TRAIN_ROWS),
    ("sc2-3b-serve-code", SERVE_ROWS), ("tiny-train-named", {
        "ce_ms.train", "grad_exchange_ms.train"})])
def test_scope_report_adds_the_named_rows_in_memory(name, rows, bench_dir,
                                                    monkeypatch):
    """The accepted cells' files do not list the rows read from names (a
    ``benchmark`` PR appends them): ``tools/scope_report.py --workload`` runs
    the cell with them added to its list, and leaves the loader as it was."""
    import importlib.util

    from benchmark import run as runner
    from benchmark.harness import common

    spec = importlib.util.spec_from_file_location(
        "scope_report", common.ROOT / "tools" / "scope_report.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    cell = common.load_cell(name, bench_dir)
    assert set(tool.named_metrics(cell, bench_dir)) == rows
    assert not rows & set(cell["per_layer"])

    seen = {}
    load_cell = common.load_cell

    def main(argv):
        seen["argv"] = argv
        seen["cell"] = common.load_cell(name, bench_dir)
        return 0

    monkeypatch.setattr(runner, "main", main)
    assert tool.run_cell(["--workload", name, "--seed", "1"]) == 0
    assert seen["argv"][-2:] == ["--trace", "1"]
    assert seen["cell"]["per_layer"] == cell["per_layer"] + sorted(rows)
    assert common.load_cell is load_cell
