"""Automatic per-leaf weight-update sharding (update_sharding='sharded',
parallel.update_sharding) + mixed-precision master weights.

The acceptance surface for ROADMAP item 2's tentpole: the sharded update
is token/loss-equivalent to the replicated update on every layout it
claims (BITWISE on the plain-DP shard_map path — XLA:CPU's
reduce-scatter sums in the same order as its all-reduce; pinned
tolerance under the extra 'seq' reduction and on GSPMD), optimizer
state lives 1/N per device, the telemetry metrics vector and the skip
guard ride the scattered update via one extra psum, the compiled HLO
carries per-leaf reduce-scatters schedulable against the backward, the
step donates every state leaf, and sharded opt state round-trips
through checkpoints across worlds AND across layouts.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neural_networks_parallel_training_with_mpi_tpu.config import (
    DataConfig, MeshConfig, ModelConfig, TrainConfig,
)
from neural_networks_parallel_training_with_mpi_tpu.ops.optim import (
    MasterState,
)
from neural_networks_parallel_training_with_mpi_tpu.parallel import (
    update_sharding as us,
)
from neural_networks_parallel_training_with_mpi_tpu.parallel.mesh import (
    make_mesh,
)
from neural_networks_parallel_training_with_mpi_tpu.train.trainer import (
    Trainer,
)
from neural_networks_parallel_training_with_mpi_tpu.utils.profiling import (
    donation_report,
)

pytestmark = pytest.mark.update_sharding


def _cfg(update_sharding, optimizer="adam", mesh=None, **kw):
    # lr small: make_regression targets are large-variance and this toy
    # diverges within a few epochs at higher lr on ANY update path
    return TrainConfig(
        nepochs=2, batch_size=16, full_batch=False, shuffle=False, lr=1e-4,
        optimizer=optimizer, update_sharding=update_sharding,
        data=DataConfig(dataset="regression", n_samples=64, n_features=8),
        model=ModelConfig(arch="mlp", in_features=8, hidden=(64, 64),
                          out_features=1),
        mesh=mesh or MeshConfig(data=8), **kw)


def _lm_cfg(update_sharding, mesh=None, **kw):
    return TrainConfig(
        nepochs=1, batch_size=8, full_batch=False, shuffle=False, lr=1e-3,
        optimizer="adam", update_sharding=update_sharding,
        loss="cross_entropy",
        data=DataConfig(dataset="lm", n_samples=32, seq_len=32,
                        vocab_size=64),
        model=ModelConfig(arch="transformer", n_layers=2, d_model=32,
                          n_heads=4, d_ff=64, vocab_size=64,
                          max_seq_len=32,
                          attention="ring" if (mesh and mesh.seq > 1)
                          else "dense"),
        mesh=mesh or MeshConfig(data=8), **kw)


def _param_leaves(t):
    return [np.asarray(x) for x in
            jax.tree_util.tree_leaves(jax.device_get(t.state.params))]


# ------------------------------------------------------------- the plan


# the one-device train step of _lm_cfg under AdamW, lowered by commit
# 5672ad5 (PR 33): PR 34's compiler options are for a TPU data mesh of more
# than one chip, so on a mesh of one ``gpt2m-train-b4``'s program and its
# compile-cache key stay.  A PR that means to change the plain
# data-parallel step re-pins this.
_ONE_DEVICE_STEP_SHA256 = (
    "a64367fefa364308f18be776d9a927564250c398acf3adebdfe1932d41c7e533")


def test_one_device_step_lowers_to_the_parents_text():
    import hashlib

    c = dataclasses.replace(_lm_cfg("replicated", mesh=MeshConfig(data=1)),
                            optimizer="adamw")
    t = Trainer(c, mesh=make_mesh(MeshConfig(data=1),
                                  devices=jax.devices()[:1]))
    t.init_state()
    batch = next(iter(t.loader.epoch(0)))
    text = t.train_step.lower(t.state, batch).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == _ONE_DEVICE_STEP_SHA256


def test_plan_largest_dim_and_tiny_fallback():
    params = {"w": jnp.zeros((48, 2048)), "e": jnp.zeros((4096, 16)),
              "b": jnp.zeros((64,)), "s": jnp.zeros(())}
    plan = us.plan_updates(params, 8)
    assert plan["w"].axis == 1 and plan["w"].padded == 2048
    assert plan["w"].shard == 256
    assert plan["e"].axis == 0
    # tiny leaves (and scalars) keep the replicated update
    assert plan["b"].axis is None and plan["s"].axis is None
    # non-divisible largest dim pads up
    plan2 = us.plan_updates({"w": jnp.zeros((2049, 3))}, 8)
    assert plan2["w"].padded == 2056 and plan2["w"].shard == 257
    # the rule is N-independent in WHICH leaves shard and along WHAT dim
    plan4 = us.plan_updates(params, 4)
    for k in params:
        assert plan4[k].axis == plan[k].axis


# ----------------------------------------------------- parity + sharding


@pytest.mark.parametrize("optimizer", [
    pytest.param("sgd", marks=pytest.mark.slow), "adam"])
def test_sharded_bitwise_matches_replicated_plain_dp(optimizer):
    """On the plain-DP shard_map path the sharded update is BITWISE
    identical to the replicated one (XLA:CPU's reduce-scatter and
    all-reduce sum in the same order; the per-shard update math is the
    same expressions on slices)."""
    ts = Trainer(_cfg("sharded", optimizer))
    rs = ts.fit()
    tr = Trainer(_cfg("replicated", optimizer))
    rr = tr.fit()
    assert rs["final_loss"] == rr["final_loss"]
    for a, b in zip(_param_leaves(ts), _param_leaves(tr)):
        np.testing.assert_array_equal(a, b)


def test_sharded_opt_state_is_one_over_n():
    t = Trainer(_cfg("sharded"))
    t.init_state()
    big = [l for l in jax.tree_util.tree_leaves(t.state.opt_state)
           if l.ndim >= 1 and l.size >= us.DEFAULT_MIN_SHARD_ELEMS]
    assert big, "toy model should still have >= 1 shardable slot"
    for l in big:
        local = int(np.prod(l.addressable_shards[0].data.shape))
        assert local * 8 == l.size, (l.shape, local)
    # params stay replicated (every device holds the full leaf)
    w = t.state.params[0]["w"]
    assert w.addressable_shards[0].data.shape == w.shape


@pytest.mark.slow
def test_sharded_dp_sp_parity_pinned_tolerance():
    """DP x SP: the scattered shard is additionally psum'd over 'seq',
    a different reduction grouping than the replicated psum over
    (data, seq) — same math, pinned f32 tolerance."""
    mesh = MeshConfig(data=4, seq=2)
    tr = Trainer(_lm_cfg("replicated", mesh=mesh))
    rr = tr.fit()
    ts = Trainer(_lm_cfg("sharded", mesh=mesh))
    rs = ts.fit()
    assert rs["final_loss"] == pytest.approx(rr["final_loss"], rel=1e-5)
    for a, b in zip(_param_leaves(tr), _param_leaves(ts)):
        np.testing.assert_allclose(a, b, rtol=1e-2, atol=5e-5)


def test_sharded_gspmd_parity_and_opt_specs():
    """GSPMD (data x fsdp): opt-state leaves carry the 'data' axis in
    their NamedShardings (the reduce-scatter/all-gather is then XLA's to
    schedule), params keep their layout, trajectory matches replicated
    at pinned tolerance."""
    mesh = MeshConfig(data=4, fsdp=2)
    tr = Trainer(_cfg("replicated", mesh=mesh))
    rr = tr.fit()
    ts = Trainer(_cfg("sharded", mesh=mesh))
    rs = ts.fit()
    assert rs["final_loss"] == pytest.approx(rr["final_loss"], rel=1e-5)
    for a, b in zip(_param_leaves(tr), _param_leaves(ts)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)
    specs = [l.sharding.spec for l in
             jax.tree_util.tree_leaves(ts.state.opt_state)]
    assert any("data" in str(s) for s in specs), specs
    # params carry no 'data' sharding (they stay batch-replicated)
    pspecs = [l.sharding.spec for l in
              jax.tree_util.tree_leaves(ts.state.params)]
    assert all("data" not in str(s) for s in pspecs), pspecs


# ------------------------------------------------- metrics + skip guard


def test_metrics_on_off_bitwise_sharded(tmp_path):
    t_on = Trainer(_cfg("sharded", telemetry_dir=str(tmp_path / "t"),
                        metrics_every=1))
    t_on.fit()
    t_off = Trainer(_cfg("sharded"))
    t_off.fit()
    for a, b in zip(_param_leaves(t_on), _param_leaves(t_off)):
        np.testing.assert_array_equal(a, b)
    recs = [json.loads(l) for l in
            open(tmp_path / "t" / "metrics.jsonl")]
    steps = [r for r in recs if r.get("kind") == "step"]
    assert steps
    for key in ("loss", "grad_norm", "param_norm", "update_ratio",
                "skipped"):
        assert key in steps[-1], steps[-1]
    assert np.isfinite(steps[-1]["grad_norm"])


def test_metrics_on_off_bitwise_zero1(tmp_path):
    """Satellite: the with_metrics + zero1 hard error is gone — the
    telemetry norms come from the scattered shard via one extra psum,
    params bitwise-identical with metrics on vs off."""
    t_on = Trainer(_cfg("zero1", optimizer="sgd",
                        telemetry_dir=str(tmp_path / "t"),
                        metrics_every=1))
    t_on.fit()
    t_off = Trainer(_cfg("zero1", optimizer="sgd"))
    t_off.fit()
    for a, b in zip(_param_leaves(t_on), _param_leaves(t_off)):
        np.testing.assert_array_equal(a, b)
    recs = [json.loads(l) for l in
            open(tmp_path / "t" / "metrics.jsonl")]
    steps = [r for r in recs if r.get("kind") == "step"]
    assert steps and "grad_norm" in steps[-1]


@pytest.mark.slow
def test_zero1_grad_norm_matches_replicated(tmp_path):
    """The scattered-shard psum norm is the SAME number the replicated
    metrics path computes from the whole tree."""
    t_z = Trainer(_cfg("zero1", optimizer="sgd",
                       telemetry_dir=str(tmp_path / "z"), metrics_every=1))
    t_z.fit()
    t_r = Trainer(_cfg("replicated", optimizer="sgd",
                       telemetry_dir=str(tmp_path / "r"), metrics_every=1))
    t_r.fit()

    def norms(d):
        return [r["grad_norm"] for r in
                (json.loads(l) for l in open(d / "metrics.jsonl"))
                if r.get("kind") == "step"]

    np.testing.assert_allclose(norms(tmp_path / "z"),
                               norms(tmp_path / "r"), rtol=1e-5)


@pytest.mark.parametrize("mode", ["sharded", "zero1"])
def test_skip_guard_on_sharded_update(mode):
    """The guard's predicate is the psum'd GLOBAL norm handed in via
    update_with_norm — a NaN batch is skipped (bitwise no-op) on the
    scattered update exactly as on the replicated one."""
    t = Trainer(_cfg(mode, optimizer="sgd", skip_nonfinite=True,
                     faults="nan@2?max=1"))
    r = t.fit()
    assert r["skipped_updates"] == 1
    assert np.isfinite(r["final_loss"])
    # clean reference: identical except the one skipped batch was clean
    t2 = Trainer(_cfg(mode, optimizer="sgd", skip_nonfinite=True))
    r2 = t2.fit()
    assert r2["skipped_updates"] == 0


@pytest.mark.slow
def test_grad_clip_inside_sharded_update():
    t = Trainer(_cfg("sharded", optimizer="sgd", grad_clip=1e-3))
    r = t.fit()
    assert np.isfinite(r["final_loss"])
    tr = Trainer(_cfg("replicated", optimizer="sgd", grad_clip=1e-3))
    rr = tr.fit()
    for a, b in zip(_param_leaves(t), _param_leaves(tr)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


# ------------------------------------------------------- master weights


def test_master_weights_bf16_params_f32_master():
    t = Trainer(_cfg("sharded", param_dtype="bfloat16",
                     master_weights=True))
    r = t.fit()
    assert np.isfinite(r["final_loss"])
    for p in jax.tree_util.tree_leaves(t.state.params):
        assert p.dtype == jnp.bfloat16
    assert isinstance(t.state.opt_state, MasterState)
    masters = jax.tree_util.tree_leaves(t.state.opt_state.master)
    assert all(m.dtype == jnp.float32 for m in masters)
    # the master (and every slot mirroring it) is scattered 1/N
    big = [m for m in masters if m.size >= us.DEFAULT_MIN_SHARD_ELEMS]
    assert big
    for m in big:
        assert int(np.prod(m.addressable_shards[0].data.shape)) * 8 \
            == m.size


def test_master_weights_tracks_f32_trajectory():
    """The defining invariant: the visible bf16 params are EXACTLY the
    cast of the f32 master (the master never loses bits; the params are
    one rounding away) — and the loss trajectory stays close to the
    all-f32 replicated run (the bf16 forward perturbs gradients at
    ~bf16 relative precision, nothing more)."""
    t = Trainer(_cfg("sharded", param_dtype="bfloat16",
                     master_weights=True))
    r = t.fit()
    masters = jax.tree_util.tree_leaves(jax.device_get(
        t.state.opt_state.master))
    for m, p in zip(masters, _param_leaves(t)):
        sl = tuple(slice(0, s) for s in p.shape)  # master is padded
        np.testing.assert_array_equal(
            np.asarray(jnp.asarray(m)[sl].astype(jnp.bfloat16)),
            np.asarray(p))
    tr = Trainer(_cfg("replicated"))
    rr = tr.fit()
    assert r["final_loss"] == pytest.approx(rr["final_loss"], rel=2e-3)


def test_bf16_params_without_master_keep_f32_slots(tmp_path):
    """--param_dtype bfloat16 WITHOUT --master_weights: slots are
    initialized f32 (the zero1 flat-buffer contract) and consume the f32
    reduce-scattered gradient, so the opt-state dtype is STABLE across
    steps — bf16-initialized slots would silently promote on step 1,
    breaking in/out aliasing (donation) and the resume template."""
    c = _cfg("sharded", param_dtype="bfloat16",
             checkpoint_dir=str(tmp_path), checkpoint_every=2)
    t = Trainer(c)
    t.init_state()
    dtypes_before = [l.dtype for l in
                     jax.tree_util.tree_leaves(t.state.opt_state)]
    assert all(d in (jnp.float32, jnp.int32) for d in dtypes_before)
    r = t.fit()
    assert np.isfinite(r["final_loss"])
    dtypes_after = [l.dtype for l in
                    jax.tree_util.tree_leaves(t.state.opt_state)]
    assert dtypes_after == dtypes_before
    for p in jax.tree_util.tree_leaves(t.state.params):
        assert p.dtype == jnp.bfloat16
    # and the resume template still matches
    t2 = Trainer(dataclasses.replace(c, nepochs=3, resume=True))
    t2.init_state()
    assert t2.maybe_resume() == r["steps"]


def test_master_weights_requires_sharded():
    with pytest.raises(ValueError, match="master_weights"):
        Trainer(_cfg("replicated", master_weights=True))
    with pytest.raises(ValueError, match="master_weights"):
        Trainer(_cfg("zero1", optimizer="sgd", master_weights=True))


def test_rejects_unsupported_combos():
    with pytest.raises(ValueError, match="adafactor"):
        Trainer(_cfg("sharded", optimizer="adafactor"))
    with pytest.raises(ValueError, match="global_mean"):
        Trainer(dataclasses.replace(_cfg("sharded"),
                                    grad_reduction="per_shard_mean"))
    with pytest.raises(NotImplementedError, match="sharded"):
        Trainer(dataclasses.replace(
            _lm_cfg("sharded"), mesh=MeshConfig(data=4, pipe=2)))


# ---------------------------------------- HLO evidence + donation audit


def _compiled_step(t):
    t.init_state()
    batch = next(iter(t.loader.epoch(0)))
    return t.train_step.lower(t.state, batch).compile(), t


def _deep_cfg(update_sharding):
    # hidden (64, 128, 64): two shardable matmul slots with DIFFERENT
    # scatter dims ((64,128) axis 1, (128,64) axis 0), so the compiled
    # program must carry >= 2 distinct per-leaf reduce-scatters — cheap
    # MLP compile
    c = _cfg(update_sharding)
    return dataclasses.replace(
        c, model=dataclasses.replace(c.model, hidden=(64, 128, 64)))


def test_hlo_reduce_scatter_overlap_evidence():
    """The sharded step's compiled HLO carries per-leaf reduce-scatters
    interleaved with backward matmuls (each depends only on its own
    leaf's gradient — the comm/compute overlap seam), where the
    replicated step has only post-backward all-reduces."""
    comp_s, t = _compiled_step(Trainer(_deep_cfg("sharded")))
    plans = jax.tree_util.tree_leaves(t.update_plan,
                                      is_leaf=us._is_plan)
    assert sum(p.axis is not None for p in plans) >= 2
    rep_s = us.collective_report(comp_s.as_text())
    assert rep_s["counts"]["reduce-scatter"] >= 2, rep_s
    assert rep_s["counts"]["all-gather"] >= 1, rep_s
    assert rep_s["overlap_schedulable"], rep_s
    assert rep_s["dots_after_first_reduce_scatter"] > 0

    comp_r, _ = _compiled_step(Trainer(_deep_cfg("replicated")))
    rep_r = us.collective_report(comp_r.as_text())
    assert rep_r["counts"]["reduce-scatter"] == 0
    assert not rep_r["overlap_schedulable"]


@pytest.mark.parametrize("mode,mesh", [
    ("replicated", None),
    ("sharded", None),
    ("sharded", MeshConfig(data=4, fsdp=2)),
])
def test_donation_audit_every_state_leaf_aliased(mode, mesh):
    """ROADMAP item 2's donation audit: the compiled step aliases EVERY
    donated state leaf in/out (no unexpected copies) — a refactor that
    silently breaks donation moves leaves into unaliased_donors and
    fails here."""
    comp, t = _compiled_step(Trainer(_cfg(mode, mesh=mesh)))
    rep = donation_report(comp)
    n_state = len(jax.tree_util.tree_leaves(t.state))
    assert rep["n_aliased"] == n_state, rep
    assert rep["unaliased_donors"] == 0, rep


@pytest.mark.slow
def test_donation_audit_dp_sp():
    comp, t = _compiled_step(
        Trainer(_lm_cfg("sharded", mesh=MeshConfig(data=4, seq=2))))
    rep = donation_report(comp)
    assert rep["n_aliased"] == len(jax.tree_util.tree_leaves(t.state))
    assert rep["unaliased_donors"] == 0


# -------------------------------------------------- checkpoint reshard


def test_checkpoint_sharded_resume_bitwise(tmp_path):
    c = _cfg("sharded", checkpoint_dir=str(tmp_path), checkpoint_every=2)
    t = Trainer(c)
    r = t.fit()
    t2 = Trainer(dataclasses.replace(c, nepochs=3, resume=True))
    t2.init_state()
    assert t2.maybe_resume() == r["steps"]
    for a, b in zip(
            [np.asarray(x) for x in jax.tree_util.tree_leaves(
                jax.device_get(t.state))],
            [np.asarray(x) for x in jax.tree_util.tree_leaves(
                jax.device_get(t2.state))]):
        np.testing.assert_array_equal(a, b)


@pytest.mark.slow
def test_checkpoint_elastic_n_to_m_reshard(tmp_path):
    """8-replica sharded snapshot restores onto a 2-replica world: the
    per-leaf padding re-derives for the new data-axis size (width 70
    pads to 72 on 8 replicas but 70 on 2 — a REAL repad, only zeros
    move), params bitwise."""
    devices = jax.devices()
    c8 = _padded_cfg("sharded", checkpoint_dir=str(tmp_path),
                     checkpoint_every=2, elastic=True)
    t8 = Trainer(c8)
    r8 = t8.fit()
    c2 = dataclasses.replace(
        _padded_cfg("sharded", mesh=MeshConfig(data=2),
                    checkpoint_dir=str(tmp_path), elastic=True,
                    resume=True), nepochs=3)
    t2 = Trainer(c2, mesh=make_mesh(MeshConfig(data=2),
                                    devices=devices[:2]))
    t2.init_state()
    # the two worlds derive different padding for the same leaf
    p8 = [l.shape for l in jax.tree_util.tree_leaves(t8.state.opt_state)]
    p2 = [l.shape for l in jax.tree_util.tree_leaves(t2.state.opt_state)]
    assert p8 != p2, "test premise: padding must differ between worlds"
    assert t2.maybe_resume() == r8["steps"]
    for a, b in zip(_param_leaves(t8), _param_leaves(t2)):
        np.testing.assert_array_equal(a, b)
    r2 = t2.fit()
    assert np.isfinite(r2["final_loss"])


def _padded_cfg(update_sharding, **kw):
    """Hidden width 70: the largest dim of the (70, 70) slot pads to 72
    on 8 replicas, so the sharded layout's opt-state shapes genuinely
    differ from the replicated ones (a width divisible by the data-axis
    size would make the conversion a no-op and prove nothing)."""
    c = _cfg(update_sharding, **kw)
    return dataclasses.replace(
        c, model=dataclasses.replace(c.model, hidden=(70, 70)))


@pytest.mark.parametrize("first,second", [("sharded", "replicated"),
                                          ("replicated", "sharded")])
def test_checkpoint_cross_layout_restore(tmp_path, first, second):
    """sharded -> replicated and replicated -> sharded ride the elastic
    reshard path (the replicated shapes are the padding-free case);
    params bitwise, training continues finite.  The model's padded
    width forces a real re-pad in both directions."""
    c1 = _padded_cfg(first, checkpoint_dir=str(tmp_path),
                     checkpoint_every=2, elastic=True)
    t1 = Trainer(c1)
    r1 = t1.fit()
    c2 = dataclasses.replace(
        _padded_cfg(second, checkpoint_dir=str(tmp_path), elastic=True,
                    resume=True), nepochs=3)
    t2 = Trainer(c2)
    t2.init_state()
    assert t2.maybe_resume() == r1["steps"]
    for a, b in zip(_param_leaves(t1), _param_leaves(t2)):
        np.testing.assert_array_equal(a, b)
    r2 = t2.fit()
    assert np.isfinite(r2["final_loss"])


def test_cross_layout_refused_without_elastic(tmp_path):
    c1 = _padded_cfg("replicated", checkpoint_dir=str(tmp_path),
                     checkpoint_every=2)
    Trainer(c1).fit()
    c2 = dataclasses.replace(
        _padded_cfg("sharded", checkpoint_dir=str(tmp_path), resume=True),
        nepochs=3)
    t2 = Trainer(c2)
    t2.init_state()
    with pytest.raises(ValueError, match="--elastic"):
        t2.maybe_resume()


@pytest.mark.slow
def test_bf16_checkpoint_refuses_f16_template(tmp_path):
    """npz stores bf16 leaves as anonymous void bytes; the snapshot
    records the TRUE dtypes (__leaf_dtypes__) so a width-matching but
    WRONG template (float16) raises the dtype mismatch instead of
    silently viewing bf16 bytes as f16 garbage."""
    c = _cfg("sharded", param_dtype="bfloat16",
             checkpoint_dir=str(tmp_path), checkpoint_every=2)
    Trainer(c).fit()
    t2 = Trainer(dataclasses.replace(c, param_dtype="float16",
                                     resume=True))
    t2.init_state()
    with pytest.raises(ValueError, match="dtype"):
        t2.maybe_resume()


@pytest.mark.slow
def test_master_weights_checkpoint_resume(tmp_path):
    c = _cfg("sharded", param_dtype="bfloat16", master_weights=True,
             checkpoint_dir=str(tmp_path), checkpoint_every=2)
    t = Trainer(c)
    r = t.fit()
    t2 = Trainer(dataclasses.replace(c, nepochs=3, resume=True))
    t2.init_state()
    assert t2.maybe_resume() == r["steps"]
    assert isinstance(t2.state.opt_state, MasterState)
    r2 = t2.fit()
    assert np.isfinite(r2["final_loss"])


# -------------------------------------------------------- SDC interplay


def test_sdc_fingerprint_skips_sharded_opt_state():
    """The SDC fingerprinter folds only REPLICATED leaves — scattered
    opt state (genuinely different per device) must not false-positive;
    params and step still get checked."""
    from neural_networks_parallel_training_with_mpi_tpu.utils import (
        consistency,
    )

    t = Trainer(_cfg("sharded"))
    t.init_state()
    fp = consistency.Fingerprinter(t.state, t.mesh)
    n_params = len(jax.tree_util.tree_leaves(t.state.params))
    # step + params are replicated; every big opt slot is scattered
    assert fp.n_leaves >= 1 + n_params
    sharded_leaves = [l for l in
                      jax.tree_util.tree_leaves(t.state.opt_state)
                      if l.size >= us.DEFAULT_MIN_SHARD_ELEMS]
    assert fp.n_leaves <= 1 + n_params + (
        len(jax.tree_util.tree_leaves(t.state.opt_state))
        - len(sharded_leaves))
    digests, _ = consistency.Fingerprinter.fetch(fp.compute(t.state))
    assert not consistency.digests_differ(digests)


@pytest.mark.slow
def test_sdc_check_trains_clean_with_sharded_update(tmp_path):
    t = Trainer(_cfg("sharded", sdc_check_every=1,
                     telemetry_dir=str(tmp_path / "t")))
    r = t.fit()
    assert np.isfinite(r["final_loss"])
    assert r.get("sdc_incidents", 0) == 0
