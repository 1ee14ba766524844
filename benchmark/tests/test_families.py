"""The family seam: the dense family's weights are the parent's bit for bit;
a configuration that names no family it can find, or whose mapping lacks a key
its family declares, is refused at load; and a second family (the toy of
``tests/data/families``, the program's Switch-style expert feed-forward) is
added to the temporary copy as files alone and runs through the unmodified
``harness/train.py`` and ``harness/serve_closed_loop.py``."""

import hashlib
import json

import numpy as np
import pytest

from conftest import BENCH

PARENT = json.loads(
    (BENCH / "tests" / "data" / "dense_weights_sha256.json").read_text())


def digest(x) -> str:
    import jax

    return hashlib.sha256(
        np.asarray(jax.device_get(x)).astype(np.float32).tobytes()
        + str(x.dtype).encode() + str(x.shape).encode()).hexdigest()


@pytest.mark.parametrize("case", sorted(PARENT))
def test_dense_weights_are_the_parents_bit_for_bit(case, bench_dir):
    """sha256 of every tensor of the two dense toys at two seeds (one above
    2**31), taken on the parent commit before the family seam was cut."""
    from benchmark.harness import common, weights

    config, seed = case.split("@")
    model = common.model_of(json.loads(
        (bench_dir / "configs" / f"{config}.json").read_text()), bench_dir)
    maker = weights.Maker(model, int(seed))
    mine = {n: digest(x) for n, x in maker.outer().items()}
    for i in range(model["n_layers"]):
        mine.update({f"L{i}.{n}": digest(x)
                     for n, x in maker.layer(i).items()})
    assert mine == PARENT[case]


def _config(bench_dir, **changes):
    config = json.loads((bench_dir / "configs" / "tiny-gpt.json").read_text())
    return {**config, **changes}


def test_unknown_family_is_refused_at_load(bench_dir):
    from benchmark.harness import common

    with pytest.raises(ValueError, match="unknown family 'sparse'"):
        common.model_of(_config(bench_dir, family="sparse"), bench_dir)
    config = _config(bench_dir)
    del config["family"]
    with pytest.raises(ValueError, match="'tiny-gpt' names no family"):
        common.model_of(config, bench_dir)


@pytest.mark.parametrize("key", ["d_ff", "vocab_size"])
def test_a_mapping_that_lacks_a_declared_key_is_refused(key, bench_dir):
    from benchmark.harness import common

    config = _config(bench_dir)
    config["mapping"] = {k: v for k, v in config["mapping"].items()
                         if k != key}
    with pytest.raises(ValueError, match=f"'tiny-gpt'.*lacks '{key}'.*'dense'"):
        common.model_of(config, bench_dir)


def test_refusals_name_what_they_refuse(bench_dir, tmp_path):
    """An ``ln_eps`` the program has no flag for names the configuration; a
    dtype without a byte count names the dtype."""
    from benchmark.harness import common
    from benchmark.reducers import counts

    cell = common.load_cell("tiny-train", bench_dir)
    model = {**cell["model"], "ln_eps": 1e-6}
    with pytest.raises(ValueError, match="'tiny-gpt' states ln_eps 1e-06"):
        model["family"].train_flags(model, cell["job"], 1, tmp_path)
    with pytest.raises(ValueError, match="'int8'"):
        counts.weight_bytes({**cell["model"], "param_dtype": "int8"})


def test_a_family_is_added_as_files_alone(bench_dir):
    """``benchmark/families`` holds ``dense`` alone; the toy family is a file
    of ``tests/data`` that the overlay copied beside it, found by the name
    its configuration gives."""
    from benchmark.harness import common

    assert sorted(p.stem for p in (BENCH / "families").glob("*.py")
                  if p.stem != "__init__") == ["dense"]
    cell = common.load_cell("tiny-switch-train", bench_dir)
    fam = cell["model"]["family"]
    assert fam.__file__ == str(bench_dir / "families" / "switch_toy.py")
    assert cell["model"]["n_experts"] == 4
    assert common.load_cell("tiny-train", bench_dir)["model"]["family"] \
        .__file__ == str(bench_dir / "families" / "dense.py")


def test_the_toy_familys_counts(bench_dir):
    """By hand: a token meets qkv 64 x 192, out 64 x 64, the router 64 x 4,
    one expert 2 x 64 x 256 in each of 2 layers, and the head 64 x 256."""
    from benchmark.harness import common, weights
    from benchmark.reducers import counts

    m = common.load_cell("tiny-switch-train", bench_dir)["model"]
    per_layer = 64 * 192 + 64 * 64 + 64 * 4 + 2 * 64 * 256
    assert counts.matmul_params(m) == 2 * per_layer + 64 * 256
    assert counts.train_flops_per_token(m, 64) == 3 * (
        2 * counts.matmul_params(m) + 4 * 2 * 64 * 64)
    expert = 2 * 64 * 256 + 256 + 64
    layer = 4 * 64 + 64 * 192 + 192 + 64 * 64 + 64 + 64 * 4 + 4 * expert
    assert weights.n_params(m) == 2 * layer + 2 * 64 * 256 + 64 * 64 + 2 * 64
    everything = (weights.n_params(m) - 64 * 256 - 64 * 64) * 4
    assert counts.weight_bytes(m) == everything
    # a tick of 2 streams reaches at most 2 of the 4 experts of each layer
    assert counts.weight_bytes(m, {"slots": 2}) == everything \
        - 2 * 2 * expert * 4
    assert counts.kv_bytes_per_token(m) == 2 * 2 * 4 * 16 * 4


def test_toy_family_train_cell_is_correct(run_cell):
    cell, _dev, res = run_cell("tiny-switch-train")
    assert res["correct"], res["checks"]
    leaves = set(res["obs"]["readings"]["ref"]["grad_norm"])
    assert {"L0.router.w", "L1.experts.w_in", "L0.q.w", "embed"} <= leaves
    assert not any("ff_in" in n for n in leaves)


def test_toy_family_that_drops_tokens_is_not_correct(run_cell):
    """The fault on the program's side: at a quarter of the capacity the
    configuration states, the program drops most tokens at the experts."""
    res = run_cell("tiny-switch-train",
                   extra_flags=["--moe_capacity_factor", "0.25"])[2]
    assert not res["correct"]
    failed = {c["name"] for c in res["checks"] if not c["ok"]}
    assert failed & {"loss_step1", "grad_norm", "change_norm"}


def test_toy_family_serve_cell_is_correct(run_cell):
    cell, _dev, res = run_cell("tiny-switch-serve", seconds=1.5)
    assert res["correct"], res["checks"]
    assert len(res["obs"]["gaps"]) > 20
