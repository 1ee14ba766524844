"""Names inside the programs, the spans' mirror in a ``jax.profiler``
capture, and the compile ledger's steady-state call.

* every ``jax.named_scope`` the device trace is read by
  (``benchmark/reducers/scopes.py``) is in the compiled HLO's ``op_name``
  of a tiny data-parallel train step and of the paged ``step`` and
  ``prefill`` programs, and the backward pass shows as
  ``transpose(jvp(`` under ``loss_and_grad``;
* a capture of three tiny train steps and three scheduler ticks holds
  ``nnpt:`` annotations, nested as the code nests them, with no
  ``Tracer`` installed;
* with a ledger installed, a call whose signature is compiled makes no
  call of ``_leaf_key``;
* the multiset of the operations' scope paths in the tiny train step and in
  the paged programs of each family's toy is pinned.
"""

import collections
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neural_networks_parallel_training_with_mpi_tpu.config import (
    DataConfig, MeshConfig, TrainConfig,
)
from neural_networks_parallel_training_with_mpi_tpu.models.transformer import (
    Transformer, TransformerConfig,
)
from neural_networks_parallel_training_with_mpi_tpu.ops import optim
from neural_networks_parallel_training_with_mpi_tpu.parallel import (
    data_parallel as dp,
)
from neural_networks_parallel_training_with_mpi_tpu.parallel import (
    update_sharding as us,
)
from neural_networks_parallel_training_with_mpi_tpu.parallel.mesh import (
    make_mesh,
)
from neural_networks_parallel_training_with_mpi_tpu.train import (
    trace as trace_lib,
)
from neural_networks_parallel_training_with_mpi_tpu.train.state import (
    TrainState,
)
from neural_networks_parallel_training_with_mpi_tpu.utils import (
    compile_ledger as ledger_lib,
)
from neural_networks_parallel_training_with_mpi_tpu.utils import prng

V, T, B = 37, 16, 8


def _op_names(compiled) -> list:
    return re.findall(r'op_name="([^"]*)"', compiled.as_text())


def _has(names, scope: str) -> bool:
    """``scope`` as one component of some op_name path, bare or inside
    ``jvp(...)`` / ``transpose(jvp(...))``."""
    rx = re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?:[)/]|$)")
    return any(rx.search(n) for n in names)


# ---- (1) scopes in the compiled programs ------------------------------------

def _train_step(devices, update_sharding):
    """The tiny data-parallel train step, lowered."""
    model = Transformer(TransformerConfig(
        vocab_size=V, max_seq_len=T, n_layers=2, d_model=16, n_heads=2,
        d_ff=32, ce_chunk=4))
    mesh = make_mesh(MeshConfig(data=4), devices=devices[:4])
    opt = optim.adamw(lr=1e-3)
    state = TrainState.create(model, opt, prng.init_key(0))
    plan = None
    if update_sharding == "zero1":
        state = state._replace(
            opt_state=dp.zero1_opt_state(opt, state.params, mesh))
    elif update_sharding == "sharded":
        plan = us.plan_updates(state.params, 4, min_shard_elems=64)
        state = us.place_state(
            state._replace(
                opt_state=us.init_opt_state(opt, state.params, plan)),
            mesh, opt, plan)
    else:
        state = dp.replicate_state(state, mesh)
    step = dp.make_train_step(model, opt, mesh, "cross_entropy",
                              donate=False, update_sharding=update_sharding,
                              update_plan=plan)
    rng = np.random.default_rng(0)
    batch = {k: rng.integers(0, V, (B, T)).astype(np.int32) for k in "xy"}
    return step.lower(state, batch)


def _train_names(devices, update_sharding):
    return _op_names(_train_step(devices, update_sharding).compile())


@pytest.fixture(scope="module")
def train_names(devices):
    return _train_names(devices, "replicated")


TRAIN_SCOPES = ["loss_and_grad", "embed", "attn_proj", "attention",
                "attn_dense", "ffn", "lm_head", "chunked_ce",
                "grad_exchange", "optimizer_update"]


@pytest.mark.parametrize("scope", TRAIN_SCOPES)
def test_train_step_hlo_names_scope(train_names, scope):
    assert _has(train_names, scope)


def test_backward_pass_is_transpose_jvp_under_loss_and_grad(train_names):
    bwd = [n for n in train_names if "transpose(jvp(" in n]
    assert bwd and all("loss_and_grad" in n for n in bwd)
    # both passes of one scope are told apart by that marker alone
    fwd = [n for n in train_names
           if "jvp(attention)" in n and "transpose(" not in n]
    assert fwd and any("transpose(jvp(attention))" in n for n in bwd)


@pytest.mark.parametrize("update_sharding", ["zero1", "sharded"])
def test_sharded_updates_name_exchange_and_update(devices, update_sharding):
    """Reduce-scatter and parameter all-gather both sit under
    ``grad_exchange``: the scope names the work, not the collective."""
    names = _train_names(devices, update_sharding)
    ex = [n for n in names if _has([n], "grad_exchange")]
    assert any("all_gather" in n for n in ex)
    assert any("psum_scatter" in n or "reduce_scatter" in n for n in ex)
    assert _has(names, "optimizer_update")


def _paged_model(**kw):
    return Transformer(TransformerConfig(
        vocab_size=64, max_seq_len=64, n_layers=2, d_model=32, n_heads=4,
        n_kv_heads=2, d_ff=64, pos_encoding="rope", **kw))


@pytest.fixture(scope="module")
def paged_names():
    from neural_networks_parallel_training_with_mpi_tpu.serve.paged_kv import (
        PagedDecodeServer,
    )

    model = _paged_model()
    srv = PagedDecodeServer(model, model.init(prng.init_key(0)), slots=2,
                            num_blocks=24, block_size=8)
    step = srv._step_fn.lower(
        srv.params, srv.pools, srv.stats, srv.tokens,
        jnp.asarray(srv.tables), srv.pos,
        jnp.asarray(srv.active), srv.key).compile()
    prefill = srv._prefill_fn.lower(
        srv.params, srv.pools, srv.stats, jnp.asarray(srv.tables[:1]),
        jnp.zeros((1,), jnp.int32), jnp.zeros((1, 8), jnp.int32),
        jnp.asarray(5, jnp.int32), jnp.asarray(True)).compile()
    return {"step": _op_names(step), "prefill": _op_names(prefill)}


PAGED_SCOPES = ["embed", "attn_proj", "attention", "paged_scatter",
                "paged_gather", "attn_core", "ffn", "lm_head"]


@pytest.mark.parametrize("scope", PAGED_SCOPES + ["sample"])
def test_paged_step_hlo_names_scope(paged_names, scope):
    assert _has(paged_names["step"], scope)


@pytest.mark.parametrize("scope", PAGED_SCOPES)
def test_paged_prefill_hlo_names_scope(paged_names, scope):
    assert _has(paged_names["prefill"], scope)


# ---- (2) the spans' mirror in a profiler capture ----------------------------

def _host_events(trace_dir) -> list:
    """(name, start_ns, end_ns) of every ``nnpt:`` event of the capture."""
    from jax.profiler import ProfileData

    path = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            out += [(e.name, int(e.start_ns),
                     int(e.start_ns) + int(e.duration_ns))
                    for e in line.events
                    if e.name.startswith(trace_lib.ANNOTATION_PREFIX)]
    return out


def _inside(events, child: str, parent: str) -> bool:
    parents = [(s, e) for n, s, e in events if n == parent]
    kids = [(s, e) for n, s, e in events if n == child]
    return bool(kids) and all(
        any(ps <= s and e <= pe for ps, pe in parents) for s, e in kids)


def test_capture_holds_nnpt_annotations_without_a_tracer(tmp_path, mesh8):
    from neural_networks_parallel_training_with_mpi_tpu.serve import (
        Scheduler, ServeConfig,
    )
    from neural_networks_parallel_training_with_mpi_tpu.train.trainer import (
        Trainer,
    )

    trainer = Trainer(TrainConfig(
        nepochs=1, batch_size=8, full_batch=False, lr=0.005,
        data=DataConfig(dataset="regression", n_samples=24)), mesh=mesh8)
    model = Transformer(TransformerConfig(
        vocab_size=32, max_seq_len=64, n_layers=1, d_model=16, n_heads=2,
        d_ff=32))
    sched = Scheduler(model, model.init(prng.init_key(0)), ServeConfig(
        slots=2, num_blocks=24, block_size=8))
    sched.submit([1, 2, 3], 8)
    sched.tick()                        # prefill and first decode compile
    assert trace_lib.active() is None
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        trainer.fit()                   # three steps of 8 rows
        for _ in range(3):
            sched.tick()
    finally:
        jax.profiler.stop_trace()
    sched.close()
    assert trace_lib.active() is None
    events = _host_events(tmp_path)
    count = lambda name: sum(1 for n, _s, _e in events if n == name)  # noqa: E731
    assert count("nnpt:dispatch") == 3 and count("nnpt:train_step") == 3
    assert count("nnpt:load") >= 3
    assert count("nnpt:decode") == 3 and count("nnpt:decode/submit") == 3
    assert _inside(events, "nnpt:dispatch", "nnpt:train_step")
    for child in ("prepare", "submit", "finish"):
        assert _inside(events, f"nnpt:decode/{child}", "nnpt:decode")
    # the between-tick gap is mirrored too, and lies outside the phases
    assert count("nnpt:sched_bubble") >= 2
    assert not _inside(events, "nnpt:sched_bubble", "nnpt:decode")


# ---- (3) the ledger's steady-state call -------------------------------------

@pytest.fixture
def ledger():
    led = ledger_lib.Ledger(None)
    ledger_lib.install(led)
    yield led
    ledger_lib.install(None)


def _count_leaf_keys(monkeypatch) -> list:
    calls = []
    real = ledger_lib._leaf_key
    monkeypatch.setattr(ledger_lib, "_leaf_key",
                        lambda x: calls.append(1) or real(x))
    return calls


def test_steady_state_call_builds_no_key(ledger, monkeypatch):
    """After the first call, N further calls of an instrumented step make
    zero calls of ``_leaf_key``; a changed shape records one compile with
    the changed component named; two known signatures are told apart by
    the one leaf they differ in."""
    fn = ledger_lib.instrument(
        jax.jit(lambda s, b: (jax.tree_util.tree_map(lambda x: x + 1, s),
                              b.sum()), donate_argnums=(0,)), "step")
    state = [jnp.zeros((2, 3)) for _ in range(40)]
    state, _ = fn(state, np.ones((4, 8), np.float32))
    calls = _count_leaf_keys(monkeypatch)
    for _ in range(5):
        state, out = fn(state, np.ones((4, 8), np.float32))
    assert calls == [] and float(out) == 32.0
    assert len(ledger.events) == 1
    # a new shape: rejected by the executable's own check BEFORE it ran
    # (the donated state is still alive), compiled fresh, recorded
    state, out = fn(state, np.ones((4, 16), np.float32))
    assert float(out) == 64.0 and float(state[0][0, 0]) == 7.0
    assert [e["n_compile"] for e in ledger.events] == [1, 2]
    assert ledger.events[1]["changed"] == {
        "[1]": {"from": "float32[4,8]", "to": "float32[4,16]"}}
    del calls[:]
    for i in range(6):
        state, _ = fn(state, np.ones((4, 8 if i % 2 else 16), np.float32))
    assert len(calls) == 6              # one probe leaf a call, not 41
    assert len(ledger.events) == 2 and float(state[0][0, 0]) == 13.0


def test_new_sharding_compiles_fresh_not_stale(ledger, devices):
    """A same-shaped argument under another sharding is another
    signature: compiled and recorded, never run through the executable
    compiled for the first placement."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh(MeshConfig(data=4), devices=devices[:4])
    fn = ledger_lib.instrument(jax.jit(lambda x: x * 2.0), "double")
    rep = jax.device_put(jnp.ones((8, 4)), NamedSharding(mesh, P()))
    shd = jax.device_put(jnp.ones((8, 4)), NamedSharding(mesh, P("data")))
    assert fn(rep).sharding == rep.sharding
    assert fn(shd).sharding.is_equivalent_to(shd.sharding, 2)
    assert len(ledger.events) == 2
    for x in (rep, shd, rep):
        assert float(fn(x)[0, 0]) == 2.0
    assert len(ledger.events) == 2


def test_failed_executable_is_not_retried(ledger):
    """An executable that fails while running re-raises the original
    error and later calls of that signature ride the jit path."""

    class Boom(RuntimeError):
        pass

    fn = ledger_lib.instrument(jax.jit(lambda x: x + 1.0), "inc")
    x = jnp.ones((3,))
    assert float(fn(x)[0]) == 2.0
    (key, compiled), = [(k, c) for k, c in fn._cache.items()]

    def broken(*_a):
        raise Boom("device lost")

    fn._cache[key] = broken
    fn._index()
    with pytest.raises(Boom):
        fn(x)
    assert fn._cache[key] is None and fn._expect == ((), {})
    assert float(fn(x)[0]) == 2.0 and len(ledger.events) == 1


# ---- (4) where the operations' time is booked -------------------------------

_NAME_LOC = re.compile(r'^loc\("([^"]*)"\(')


def _scope_paths(lowered) -> str:
    """The multiset of a lowered program's operations' name-stack paths
    (scopes and primitive, no file or line; "" for an operation without
    one): a ``count path`` line a path, sorted."""
    paths = collections.Counter()

    def walk(op):
        for region in op.regions:
            for block in region.blocks:
                for inner in block.operations:
                    m = _NAME_LOC.match(str(inner.location))
                    paths[m.group(1) if m else ""] += 1
                    walk(inner)

    walk(lowered.compiler_ir().operation)
    return "".join(f"{n} {p}\n" for p, n in sorted(paths.items()))


# the toys whose training step and paged programs are pinned: the model above
# in bfloat16 (grouped K/V, one kind of layer; the casts a float32 toy lacks),
# and each family's test toy (its module's MODEL): latent attention, window
# and full layers with routing without drops, YaRN beside plain rotary with
# routing without drops, a mixer beside the attention, and delta-rule layers
_FAMILIES = {"dense_gqa": None, "latent": "mla_moe_model",
             "window_moe": "gqa_window_moe_model",
             "yarn_moe": "window_yarn_moe_model",
             "mixer": "ssm_hybrid_model", "delta": "olmo_hybrid_model"}

# the scope paths as commit 61f07d9 lowered them, before the paged programs
# ran the model's own block with the cache's cores passed in: every
# per-layer metric that reads device time by scope (the serving cells', and
# the train step's ``attention_ms`` / ``fwd_ms`` / ``bwd_ms``) reads the same
# operations under the same names as long as these hold.  A change that means
# to move an operation to another scope re-pins what it moves.
_SCOPES_SHA256 = {
    "delta.decode":
        "9c8d5728d2dbf3779257f3806c658e97ab84e479bfba92a895f501bc544f0fac",
    "delta.prefill":
        "ad2ea53602df83ad8d58834265eb7d0fc9dbcfa1fd6afa0e10dd88de0552340a",
    "delta.train":
        "7b9dd5858b89a34bf19cf75974943e406a8e2838dbaf0451b6d4421f34c6e780",
    "dense_gqa.decode":
        "4c76c697aec35ee26bbd7470e0ae657af07ac92038ed71214cbedfc5187060d4",
    "dense_gqa.prefill":
        "96cdcc70781edde3b989a02df723410a618f2e09c4dcb98487b0230a2aecdc2d",
    "dense_gqa.train":
        "666cc5a405b45a162aacfc2f4b7508a055e9453bb62afe0e4c598132ac08770d",
    "latent.decode":
        "8cb1ffe99472499521df6da327162f1b23f27304fce8dae6b41a57c50f1488a4",
    "latent.prefill":
        "2956dc1bfb7fbc1aa16499a8a4da136fb699bba5daf2364462ab3e29e1d19cdf",
    "latent.train":
        "435bde5b34630e8fc17c349faf17dcade2eea740496994a011f4a4884abffd14",
    "mixer.decode":
        "053e32d453f1c3ddaef6dbe6f8df5634f04fa512ad1f4358074a8847f86fc684",
    "mixer.prefill":
        "7d311f86a299f542338f16cdbea5a57017a7aae98d7867605ac555ed844fb3e2",
    "mixer.train":
        "efd9f54e66369f9fd4d6d8c65b655b8e6bcf9e79be5c76d437c09664bf9394cf",
    "train":
        "ae2cc0a3a813fd8d66ff8148d90e842e6a479a73e5c4e74bd75d24486f43bc14",
    "window_moe.decode":
        "a0555e681740303ab834038108c65ce55bf7f583da85d040b9bde996a6421ac0",
    "window_moe.prefill":
        "ec8fe0cfde37aa3218b520410a1a41f26493e0611fa18d47efe4da296293c03d",
    "window_moe.train":
        "c682f14be77f2244813b072cc349e238c83d43f5280ee0668aaf2ba9bb3981e3",
    "yarn_moe.decode":
        "19dc682a301ceeedf93f6f482fba7ec1d95c7d02dbc6223b802ac6058c586378",
    "yarn_moe.prefill":
        "7ab61f2d755ba3bcc6dd9d5a7314ee01ca5a56313486f5b2baa8ca014cd0ce2c",
    "yarn_moe.train":
        "d82495936721bc3aac644fca12345a82ed04e9315831d9453c73edcf85d1abc4",
}


@pytest.fixture(scope="module")
def served(lowered_programs):
    import importlib

    got = {}

    def programs(family):
        if family not in got:
            module = _FAMILIES[family]
            if module is None:
                got[family] = lowered_programs(
                    _paged_model(compute_dtype=jnp.bfloat16), slots=2,
                    num_blocks=24, block_size=8)
            else:
                model = importlib.import_module(f"tests.test_{module}").MODEL
                got[family] = lowered_programs(
                    model["family"].program_model(model), slots=2,
                    num_blocks=48, block_size=4, max_len=64,
                    prefill_chunk=8)
        return got[family]

    return programs


@pytest.mark.parametrize("program", sorted(_SCOPES_SHA256))
def test_scope_paths_are_the_parents(program, devices, served):
    if program == "train":
        lowered = _train_step(devices, "replicated")
    else:
        family, name = program.split(".")
        lowered = served(family)[name]
    assert hashlib.sha256(_scope_paths(lowered).encode()).hexdigest() \
        == _SCOPES_SHA256[program]
