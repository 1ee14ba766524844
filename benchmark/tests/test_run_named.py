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
    ("gpt2m-train-b4", TRAIN_ROWS - {"grad_exchange_ms.train"}),
    ("gpt2m-train-dp4", TRAIN_ROWS), ("sc2-3b-serve-code", SERVE_ROWS),
    ("tiny-train-named", TRAIN_ROWS - {"ce_ms.train",
                                       "grad_exchange_ms.train"})])
def test_cells_list_the_rows_read_from_names(name, rows, bench_dir):
    """The accepted cells' own files list the scope and idle rows (the
    exchange's only where there are chips to exchange between), so plain
    ``benchmark/run.py --trace 1`` reports them and nothing adds to a cell's
    list in memory; each row's file names a reducer that reads names."""
    from benchmark.harness import common

    cell = common.load_cell(name, bench_dir)
    assert rows <= set(cell["per_layer"])
    assert len(set(cell["per_layer"])) == len(cell["per_layer"])
    for row in rows:
        spec = common.load_metric(row, bench_dir)
        assert spec["reducer"].split(":")[0] in ("scopes", "host_phases")
