"""Described-chip compiles: the main path's kernels and serving programs
lowered for a TPU v5e that is described, not attached.

Nothing here runs — a compile says the chip's compiler accepts the
program at ``big_lm`` head geometry (16 heads x head_dim 64, d_model
1024, bf16), which interpret mode cannot say: the fused paged kernel
passed every interpret-mode test while Mosaic refused it outright.  Every
kernel is lowered with ``interpret=False`` passed explicitly (the default
asks ``jax.default_backend()``, which is the CPU here).

The topology is described inside a module-scoped fixture — never at
import, never in conftest — so under pytest-xdist only the worker that is
handed this file loads the TPU compiler, and every worker collects the
same tests.  All such compiles live in this one file for the same reason.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from neural_networks_parallel_training_with_mpi_tpu.models.transformer import (
    Transformer, TransformerConfig,
)
from neural_networks_parallel_training_with_mpi_tpu.ops.pallas_kernels import (
    flash_attention, paged_attention,
)
from neural_networks_parallel_training_with_mpi_tpu.serve import paged_kv
from neural_networks_parallel_training_with_mpi_tpu.utils import prng

# big_lm widths and the serving geometry are chip_smoke.py's
BATCH, SEQ, HEADS, HEAD_DIM, D_MODEL = 8, 1024, 16, 64, 1024
SLOTS, NUM_BLOCKS, BLOCK_SIZE, PREFILL = 8, 513, 16, 128
MAX_BLOCKS = SEQ // BLOCK_SIZE
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a described-chip executable is written to the persistent cache but
    # cannot be read back without a chip; keep these compiles out of it
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)``: an abstract array placed on one described
    chip (there is no device to hold a real one)."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_forward(spec):
    qkv = spec((BATCH, SEQ, HEADS, HEAD_DIM), BF16)
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, True, 128, 128, False),
        qkv, qkv, qkv)
    assert "tpu_custom_call" in text


def test_flash_backward(spec):
    qkv = spec((BATCH, SEQ, HEADS, HEAD_DIM), BF16)

    def loss(q, k, v):
        out = flash_attention(q, k, v, True, 128, 128, False)
        return out.astype(jnp.float32).sum()

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)
    # forward + dq + dk/dv kernels
    assert text.count("custom_call_target=\"tpu_custom_call\"") >= 3


def test_flash_at_the_cells_shape_and_derived_blocks(spec):
    """The training cells' own attention (B 4, T 1024, 16 x 64, bf16), at
    the tiling the code derives, and ``starcoder2-3b``'s head beside it:
    three kernels each, inside the chip's VMEM."""
    for heads, head_dim in ((16, 64), (24, 128)):
        qkv = spec((4, 1024, heads, head_dim), BF16)

        def loss(q, k, v):
            out = flash_attention(q, k, v, True, None, None, False)
            return out.astype(jnp.float32).sum()

        text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)),
                              qkv, qkv, qkv)
        assert text.count("custom_call_target=\"tpu_custom_call\"") >= 3


@pytest.mark.parametrize("kernel", ["flash", "paged"])
def test_kernel_lowering_does_not_move_with_path_or_lines(spec, tmp_path,
                                                          kernel):
    """What the compile cache keys a program by must not move with the
    checkout's path or a comment line in ``ops/pallas_kernels.py``
    (ROADMAP S6, "PR 22 run C"): the kernels' lowering, Mosaic payload and
    locations included, is byte-identical from a copy of the file under
    another path with its lines shifted, given the settings of
    ``utils.platform.compile_cache`` — the flash kernels of the train step
    and the paged kernel of the serving programs alike."""
    import importlib.util

    from neural_networks_parallel_training_with_mpi_tpu.ops import (
        pallas_kernels,
    )

    src = open(pallas_kernels.__file__).read()
    moved = tmp_path / "elsewhere" / "pallas_kernels.py"
    moved.parent.mkdir()
    moved.write_text("# a comment line\n# and another\n" + src)
    mod_spec = importlib.util.spec_from_file_location("moved_kernels", moved)
    copy = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(copy)

    if kernel == "flash":
        qkv = spec((2, 256, 2, 64), BF16)

        def lowered(mod):
            def loss(q, k, v):
                with jax.named_scope("attention"):
                    out = mod.flash_attention(q, k, v, True, 128, 128, False)
                return out.astype(jnp.float32).sum()

            return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
                qkv, qkv, qkv).as_text(debug_info=True)
    else:
        pool = spec((65, 16, 256), BF16)
        args = (spec((4, 1, 24, 128), BF16), pool, pool,
                spec((4, 16), jnp.int32), spec((4,), jnp.int32),
                spec((4,), jnp.int32))

        def lowered(mod):
            def fn(q, kp, vp, tables, lens, starts):
                with jax.named_scope("attention"):
                    return mod.paged_attention(q, kp, vp, tables, lens,
                                               starts, interpret=False)

            return jax.jit(fn).lower(*args).as_text(debug_info=True)

    limit = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        here, there = lowered(pallas_kernels), lowered(copy)
    finally:
        jax.config.update("jax_traceback_in_locations_limit", limit)
    assert "tpu_custom_call" in here and "attention" in here
    assert here == there


@pytest.mark.parametrize("width,int8_kv", [
    (1, False), (PREFILL, False), (1, True), (PREFILL, True),
], ids=["decode", "prefill128", "decode-int8kv", "prefill128-int8kv"])
def test_paged_attention(spec, width, int8_kv):
    """The fused paged kernel (``attn_impl="fused"``): width 1 is the
    batched decode step, width 128 one chunked-prefill bucket."""
    pool = spec((NUM_BLOCKS, BLOCK_SIZE, HEADS, HEAD_DIM),
                jnp.int8 if int8_kv else BF16)
    args = [spec((SLOTS, width, HEADS, HEAD_DIM), BF16), pool, pool,
            spec((SLOTS, MAX_BLOCKS), jnp.int32), spec((SLOTS,), jnp.int32),
            spec((SLOTS,), jnp.int32)]
    if int8_kv:
        scale = spec((NUM_BLOCKS, BLOCK_SIZE, HEADS), jnp.float32)
        args += [scale, scale]

    def fn(q, kp, vp, tables, lens, starts, ks=None, vs=None):
        return paged_attention(q, kp, vp, tables, lens, starts, k_scale=ks,
                               v_scale=vs, interpret=False)

    assert "tpu_custom_call" in _compiled_text(fn, *args)


def test_paged_attention_shared_row(spec):
    """The kernel's shared-row mode at ``ms4-119b-ep4-serve-docqa``'s decode
    shapes: 32 streams of 32 query rows against one row stored 384 lanes
    wide, the value its first 256, tables of 544 blocks (68 KB of scalar
    prefetch) over a pool of 17409."""
    args = [spec((32, 1, 32, 384), BF16), spec((17409, 16, 384), BF16),
            spec((32, 544), jnp.int32), spec((32,), jnp.int32),
            spec((32,), jnp.int32)]

    def fn(q, pool, tables, lens, starts):
        return paged_attention(q, pool, None, tables, lens, starts,
                               v_lanes=256, scale=0.1, interpret=False)

    assert "tpu_custom_call" in _compiled_text(fn, *args)


@pytest.fixture(scope="module")
def serve_programs(spec):
    """The gathered paged server's jitted programs over a 2-layer model
    at big_lm widths, with abstract params and pools on the described
    chip."""
    model = Transformer(TransformerConfig(
        vocab_size=32768, max_seq_len=SEQ, n_layers=2, d_model=D_MODEL,
        n_heads=HEADS, d_ff=4096, compute_dtype=BF16))
    abstract = lambda tree: jax.tree_util.tree_map(      # noqa: E731
        lambda x: spec(x.shape, x.dtype), tree)
    params = abstract(jax.eval_shape(
        lambda: model.init(prng.init_key(0))))
    pools = abstract(jax.eval_shape(
        lambda: paged_kv.init_paged_kv(model, NUM_BLOCKS, BLOCK_SIZE)))
    prefill, step, _, _ = paged_kv._paged_programs(
        model, BLOCK_SIZE, MAX_BLOCKS, 0.0, 0, 1.0, False, "gathered")
    return params, pools, prefill, step


def test_gathered_decode_step(spec, serve_programs):
    params, pools, _, step = serve_programs
    step.lower(
        params, pools, {}, spec((SLOTS, SEQ), jnp.int32),
        spec((SLOTS, MAX_BLOCKS), jnp.int32), spec((SLOTS,), jnp.int32),
        spec((SLOTS,), jnp.bool_), spec((2,), jnp.uint32)).compile()


def test_gathered_prefill_bucket(spec, serve_programs):
    params, pools, prefill, _ = serve_programs
    prefill.lower(
        params, pools, {}, spec((1, MAX_BLOCKS), jnp.int32),
        spec((1,), jnp.int32), spec((1, PREFILL), jnp.int32),
        spec((), jnp.int32), spec((), jnp.bool_)).compile()


# ---- the paged kernel in the serving programs, at sc2-3b-serve-code's shapes --

CELL = dict(slots=16, heads=24, kv_heads=2, head_dim=128, block_size=16,
            max_blocks=256, num_blocks=2305, chunk=512)


@pytest.fixture(scope="module")
def cell_programs(spec):
    """The paged server's programs over 2 layers of ``starcoder2-3b`` (d
    3072, 24 heads over 2 KV heads of 128, FFN 12288, rotary, bf16) with the
    cell's geometry, as ``attn_impl="auto"`` resolves them on a TPU: the
    kernel over pools stored with the heads folded into the lanes."""
    from unittest import mock

    model = Transformer(TransformerConfig(
        vocab_size=49152, max_seq_len=16384, n_layers=2, d_model=3072,
        n_heads=CELL["heads"], n_kv_heads=CELL["kv_heads"], d_ff=12288,
        pos_encoding="rope", rope_theta=999999.44, param_dtype=BF16,
        compute_dtype=BF16))
    abstract = lambda tree: jax.tree_util.tree_map(      # noqa: E731
        lambda x: spec(x.shape, x.dtype), tree)
    params = abstract(jax.eval_shape(lambda: model.init(prng.init_key(0))))
    # ``auto`` and the kernel's ``interpret`` default both ask the default
    # backend, which is the CPU here: say TPU while the programs are built
    # and lowered (the kernels_compiled fixture of the latent tests, held
    # for the module)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        assert paged_kv.resolve_attn_impl(model, "auto") == "fused"
        pools = abstract(jax.eval_shape(lambda: paged_kv.init_paged_kv(
            model, CELL["num_blocks"], CELL["block_size"], folded=True)))
        prefill, step, _, _ = paged_kv._paged_programs(
            model, CELL["block_size"], CELL["max_blocks"], 0.0, 0, 1.0,
            False, "auto")
        s, mb = CELL["slots"], CELL["max_blocks"]
        lowered = {
            "decode": step.lower(
                params, pools, {}, spec((s, mb * CELL["block_size"]),
                                        jnp.int32),
                spec((s, mb), jnp.int32), spec((s,), jnp.int32),
                spec((s,), jnp.bool_), spec((2,), jnp.uint32)),
            "prefill": prefill.lower(
                params, pools, {}, spec((1, mb), jnp.int32),
                spec((1,), jnp.int32), spec((1, CELL["chunk"]), jnp.int32),
                spec((), jnp.int32), spec((), jnp.bool_))}
    return lowered


_POOL_SHAPED = r"\[2305,16,(?:256|2,128)\]"


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_cell_programs_run_the_kernel_over_the_pool_in_place(cell_programs,
                                                             program):
    """The decode program and a 512-token prefill bucket compile with the
    paged kernel; the kernel is lowered once a program, not once a layer
    (one ``tpu_custom_call`` in the lowered text, in a private function
    each layer calls; one a layer once XLA has inlined it); and nothing
    pool-shaped is copied, transposed or gathered: each pool goes from the
    program's parameter through its scatter into the kernel and out, in
    the layout it is stored in."""
    import re

    lowered = cell_programs[program]
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "paged_attention" in text
    compiled = lowered.compile().as_text()
    assert compiled.count('custom_call_target="tpu_custom_call"') == 2
    assert "paged_gather" not in compiled
    moved = [line.strip()[:160] for line in compiled.splitlines()
             if re.search(r"= \S*" + _POOL_SHAPED
                          + r"\S* (?:copy|transpose|gather|copy-start)\(",
                          line)]
    assert not moved, moved
    # the pools reach the kernel as the scatter leaves them: 2 layers x
    # (k, v) scatter fusions, each feeding a custom call
    assert len(re.findall(r"= \S*" + _POOL_SHAPED + r"\S* fusion\(",
                          compiled)) == 4


# ---- latent attention + routing without drops, at the published widths ----

@pytest.fixture(scope="module")
def latent_programs(spec):
    """The paged server's programs over 2 layers of the latent-attention,
    routed block at its published widths (d 4096, 32 heads, ranks 1024 / 256,
    128 experts of width 2048 of which 32 are held, top 4), abstract params
    and pools on the described chip, with the serving cell's geometry (32
    slots, 8704 positions).  The kernels are Pallas, as on the chip:
    ``auto`` asks the default backend, which is the CPU here."""
    from unittest import mock

    from neural_networks_parallel_training_with_mpi_tpu.ops.rope import (
        RopeScaling,
    )

    model = Transformer(TransformerConfig(
        vocab_size=32768, max_seq_len=8704, n_layers=2, d_model=4096,
        n_heads=32, d_ff=2048, pos_encoding="rope", norm="rmsnorm",
        norm_eps=1e-6, use_bias=False, attention_kind="mla",
        q_lora_rank=1024, kv_lora_rank=256, qk_nope_head_dim=64,
        qk_rope_head_dim=64, v_head_dim=128,
        rope_scaling=RopeScaling(128.0, 8192, 32.0, 1.0, 1.0, 1.0, 0.1),
        moe_experts=128, moe_top_k=4, moe_dropless=True,
        moe_experts_held=(0, 32), moe_shared_ff=2048,
        param_dtype=BF16, compute_dtype=BF16))
    abstract = lambda tree: jax.tree_util.tree_map(      # noqa: E731
        lambda x: spec(x.shape, x.dtype), tree)
    params = abstract(jax.eval_shape(lambda: model.init(prng.init_key(0))))
    # as ``attn_impl="auto"`` resolves them on a TPU: the paged kernel's
    # shared-row mode in the decode program, over pools stored 384 lanes wide
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        assert paged_kv.resolve_attn_impl(model, "auto") == "fused"
        pools = abstract(jax.eval_shape(
            lambda: paged_kv.init_paged_kv(model, 17409, 16, folded=True)))
        prefill, step, _, _ = paged_kv._paged_programs(
            model, 16, 544, 0.0, 0, 1.0, False, "auto")
    assert {n: p.shape for n, p in pools[0].items()} \
        == {"latent": (17409, 16, 384)}
    stats = {"experts": spec((len(paged_kv.EXPERT_COUNTERS),), jnp.int32)}
    return params, pools, stats, prefill, step


@pytest.fixture
def kernels_compiled(monkeypatch):
    """Lower the Pallas kernels (the grouped matmul, the paged walk) for
    the chip, not interpreted."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _latent_pool_moved(compiled_text: str) -> list:
    """Operations that copy, transpose or gather something pool-shaped."""
    import re

    shaped = r"\[17409,(?:16,(?:320|384)|(?:320|384),16)\]"
    return [line.strip()[:160] for line in compiled_text.splitlines()
            if re.search(r"= \S*" + shaped
                         + r"\S* (?:copy|transpose|gather|copy-start)\(",
                         line)]


def test_latent_routed_decode_step(spec, latent_programs, kernels_compiled):
    """The decode tick reads the latent pool in place: the paged kernel
    between the two ``mla_absorb`` products, no scope ``paged_gather``, and
    nothing pool-shaped copied, transposed or gathered (the 320-lane pool
    was transposed whole before and after its scatter in every layer: PERF.md
    section 6, PR 32)."""
    params, pools, stats, _, step = latent_programs
    lowered = step.lower(
        params, pools, stats, spec((32, 8704), jnp.int32),
        spec((32, 544), jnp.int32), spec((32,), jnp.int32),
        spec((32,), jnp.bool_), spec((2,), jnp.uint32))
    assert "paged_attention" in lowered.as_text()
    text = lowered.compile().as_text()
    for scope in ("mla_absorb", "moe_route", "moe_experts", "moe_shared",
                  "moe_combine", "paged_scatter", "paged_attention_fused"):
        assert scope in text, scope
    assert "paged_gather" not in text
    assert "gmm" in text
    assert not _latent_pool_moved(text)


def test_latent_routed_prefill_chunk(spec, latent_programs, kernels_compiled):
    """A 1024-token chunk: the expanded form walks the keys in a loop whose
    trip count is the chunk's own length in blocks, so the (32, 1024, 8704)
    float32 scores are never whole (3.7 GB of temporaries before, 0.34
    after, by the compiler's count at 6 layers); its one stream's rows are
    gathered from the pool as stored, and the pool itself is not copied."""
    params, pools, stats, prefill, _ = latent_programs
    compiled = prefill.lower(
        params, pools, stats, spec((1, 544), jnp.int32),
        spec((1,), jnp.int32), spec((1, 1024), jnp.int32),
        spec((), jnp.int32), spec((), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "while" in text and "moe_experts" in text
    assert "paged_gather" in text and not _latent_pool_moved(text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


# ---- window and full layers over two kinds of cache, at the published widths -

WIDE = dict(slots=48, heads=64, kv_heads=8, head_dim=128, window=128,
            block_size=16, max_blocks=1088, num_blocks=48 * 1088 + 1,
            chunk=1024)


@pytest.mark.parametrize("width,window", [(1, None), (1, 128), (1024, None),
                                          (1024, 128)],
                         ids=["decode", "decode_window", "chunk",
                              "chunk_window"])
def test_paged_attention_at_the_wide_rows_both_walks(spec, width, window):
    """The kernel at ``kexaone-236b-ep8-serve-longmix``'s shapes: 64 query
    heads over 8 KV heads of 128 (a 1024-lane row, a 32 KB page a pool), 48
    tables of 1088 blocks (209 KB of scalar prefetch), the full walk and the
    walk bounded below by the window, with the tilings the table holds."""
    c = WIDE
    s = c["slots"] if width == 1 else 1
    lanes = c["kv_heads"] * c["head_dim"]
    args = [spec((s, width, c["heads"], c["head_dim"]), BF16),
            spec((c["num_blocks"], 16, lanes), BF16),
            spec((c["num_blocks"], 16, lanes), BF16),
            spec((s, c["max_blocks"]), jnp.int32), spec((s,), jnp.int32),
            spec((s,), jnp.int32)]

    def fn(q, kp, vp, tables, lens, starts):
        return paged_attention(q, kp, vp, tables, lens, starts,
                               window=window, interpret=False)

    assert "tpu_custom_call" in _compiled_text(fn, *args)


@pytest.fixture(scope="module")
def kinds_programs(spec):
    """The paged server's programs over 2 layers of the model with window and
    full layers at its published widths (d 6144, 64 heads over 8 KV heads of
    128, the per-head norm, a dense layer of width 18432 with window
    attention, then a sparse layer with full attention without positions:
    128 experts of width 2048 of which 16 are held, sigmoid top 8) and the
    cell's geometry; ``auto`` resolved as on a TPU."""
    from unittest import mock

    c = WIDE
    model = Transformer(TransformerConfig(
        vocab_size=19200, max_seq_len=262144, n_layers=2, d_model=6144,
        n_heads=c["heads"], n_kv_heads=c["kv_heads"],
        head_width=c["head_dim"], d_ff=2048, activation="swiglu",
        pos_encoding="rope", rope_theta=1e6, norm="rmsnorm", norm_eps=1e-5,
        use_bias=False, qk_norm=True, attention_pattern="LG",
        sliding_window=c["window"], rope_global=False, moe_experts=128,
        moe_top_k=8, moe_dropless=True, moe_experts_held=(0, 16),
        moe_shared_ff=2048, moe_score="sigmoid", moe_routed_scale=2.5,
        moe_first_dense=1, dense_ff=18432, param_dtype=BF16,
        compute_dtype=BF16))
    abstract = lambda tree: jax.tree_util.tree_map(      # noqa: E731
        lambda x: spec(x.shape, x.dtype), tree)
    params = abstract(jax.eval_shape(lambda: model.init(prng.init_key(0))))
    blocks = paged_kv.window_pool_blocks(c["window"], c["slots"],
                                         c["block_size"], c["chunk"])
    assert blocks == 1 + 48 * 9 + 64
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        assert paged_kv.resolve_attn_impl(model, "auto") == "fused"
        pools = abstract(jax.eval_shape(lambda: paged_kv.init_paged_kv(
            model, c["num_blocks"], c["block_size"], folded=True,
            window_blocks=blocks)))
        prefill, step, _, _ = paged_kv._paged_programs(
            model, c["block_size"], c["max_blocks"], 0.0, 0, 1.0, False,
            "auto")
    # the window layer's pool: 497 blocks, 32.6 MB a layer, whatever max_len
    assert [p["k"].shape for p in pools] == [(497, 16, 1024),
                                             (c["num_blocks"], 16, 1024)]
    stats = {"experts": spec((len(paged_kv.EXPERT_COUNTERS),), jnp.int32),
             "attention": spec((len(paged_kv.ATTENTION_COUNTERS),),
                               jnp.int32)}
    return params, pools, stats, prefill, step


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_kinds_programs_walk_both_kinds_of_cache_in_place(
        spec, kinds_programs, kernels_compiled, program):
    """The decode tick and a 1024-token chunk compile with the paged kernel
    in both kinds of layer (two kernels lowered a program: the full walk and
    the bounded one), each under its kind's scope inside ``attn_core``, the
    per-head norm under ``qk_norm``; nothing pool-shaped is copied,
    transposed or gathered, of either kind's pool."""
    import re

    params, pools, stats, prefill, step = kinds_programs
    c = WIDE
    s, mb = c["slots"], c["max_blocks"]
    tabs = lambda n: (spec((n, mb), jnp.int32), spec((n, mb), jnp.int32))  # noqa: E731
    if program == "decode":
        lowered = step.lower(
            params, pools, stats, spec((s, mb * 16), jnp.int32), tabs(s),
            spec((s,), jnp.int32), spec((s,), jnp.bool_),
            spec((2,), jnp.uint32))
    else:
        lowered = prefill.lower(
            params, pools, stats, tabs(1), spec((1,), jnp.int32),
            spec((1, c["chunk"]), jnp.int32), spec((), jnp.int32),
            spec((), jnp.bool_))
    assert lowered.as_text().count("tpu_custom_call") >= 2
    compiled = lowered.compile()
    text = compiled.as_text()
    for scope in ("attn_core/attn_window/paged_attention_fused",
                  "attn_core/attn_full/paged_attention_fused", "qk_norm",
                  "moe_route", "moe_experts", "paged_scatter"):
        assert scope in text, scope
    assert "paged_gather" not in text
    shaped = r"\[(?:497|52225),16,1024\]"
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(r"= \S*" + shaped
                          + r"\S* (?:copy|transpose|gather|copy-start)\(",
                          line)]
    assert not moved, moved
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


# ---- the programs of falconh1-34b-pp12-serve-chat, whole -------------------


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_hybrid_cell_programs_fit_the_chip(spec, kernels_compiled, program):
    """The decode tick (64 slots) and a 512-column prefill chunk of the
    cell's own configuration (``benchmark/configs/falcon-h1-34b-pp12.json``:
    6 layers at the published widths, the whole vocabulary; the mix's 8193
    blocks of 16 and 192 table entries a stream), with the state store beside
    the pools, compile for the described chip under its 15.75 GB.  Counted
    here: the tick 13.77 GB (PR 35: 10.51 of weights, 1.61 of pool, 1.61 of
    state, 25 MB of temporaries), the chunk 13.88 GB (PR 38: 132 MB of
    temporaries; its head runs on the last true column alone, under a
    ``conditional`` the compiler keeps, so no logits of the chunk's 512
    columns exist; 14.33 GB with them).  The mixer's scopes are in the
    compiled text, the tick's update of a layer's state is one fusion that
    reads the store once, and neither a pool nor the state is copied."""
    import re
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from benchmark.harness import common

    cell = common.load_cell("falconh1-34b-pp12-serve-chat")
    model = cell["model"]["family"].program_model(cell["model"])
    geo = cell["job"]["serve_config"]
    s, bs = geo["slots"], geo["block_size"]
    mb = geo["max_len"] // bs
    abstract = lambda tree: jax.tree_util.tree_map(      # noqa: E731
        lambda x: spec(x.shape, x.dtype), tree)
    params = abstract(jax.eval_shape(lambda: model.init(prng.init_key(0))))
    assert paged_kv.resolve_attn_impl(model, "auto") == "fused"
    pools = abstract(jax.eval_shape(lambda: paged_kv.init_paged_kv(
        model, geo["num_blocks"], bs, folded=True)))
    state = abstract(jax.eval_shape(
        lambda: paged_kv.init_paged_state(model, s)))
    assert [(v["conv"].shape, v["ssm"].shape, v["ssm"].dtype)
            for v in state] == [((64, 3, 5120), (64, 32, 128, 256),
                                 jnp.float32)] * 6
    prefill, step, _, _ = paged_kv._paged_programs(
        model, bs, mb, 0.0, 0, 1.0, False, "auto")
    stats = {"ssm": spec((len(paged_kv.SSM_COUNTERS),), jnp.int32)}
    if program == "decode":
        lowered = step.lower(
            params, pools, state, stats, spec((s, mb * bs), jnp.int32),
            spec((s, mb), jnp.int32), spec((s,), jnp.int32),
            spec((s,), jnp.bool_), spec((2,), jnp.uint32))
    else:
        lowered = prefill.lower(
            params, pools, state, stats, spec((1, mb), jnp.int32),
            spec((), jnp.int32), spec((1,), jnp.int32),
            spec((1, geo["prefill_chunk"]), jnp.int32), spec((), jnp.int32),
            spec((), jnp.bool_))
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 13.5e9 < total < 14.0e9 < 15.75e9, total
    text = compiled.as_text()
    if program == "prefill":
        assert "f32[1,512,261120]" not in text
        assert "f32[1,261120]" in text and " conditional(" in text
    scopes = ("ssm_in", "ssm_conv", "ssm_gate_norm", "ssm_out",
              "ssm_update" if program == "decode" else "ssm_scan",
              "attn_core/paged_attention_fused", "paged_scatter")
    for scope in scopes:
        assert f"/{scope}/" in text, scope
    assert "paged_gather" not in text
    # (the convolution's tails, 2 MB a layer, the compiler does lay out anew)
    shaped = r"\[(?:8193,16,512|64,32,128,256)\]"
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(r"= \S*" + shaped
                          + r"\S* (?:copy|transpose|gather|copy-start)\(",
                          line)]
    assert not moved, moved
    if program == "decode":
        # one fusion a layer makes the new state and the state's read
        # (S . C) from one pass over the store
        both = [line for line in text.splitlines()
                if "/ssm_update/" in line and " fusion(" in line
                and "f32[64,32,128,256]" in line.split(" fusion(")[0]]
        assert len(both) == 6, len(both)
        assert all("f32[64,32,128]" in line.split(" fusion(")[0]
                   for line in both)


def test_hybrid_cell_boundary_programs_update_in_place(spec):
    """The three programs of a request's boundary (``serve_admit``,
    ``serve_first_token`` for the one row of float32 logits a prompt's last
    chunk returns, ``serve_take``) at the cell's own sizes, for the described
    chip: the admission zeroes one slot's rows of the 1.61 GB state store in
    place (the store is aliased to its output and no layer's state is
    copied: the cell has 1.2 GB of room, not 1.6), and all three are small
    beside any model program."""
    import re
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from benchmark.harness import common

    cell = common.load_cell("falconh1-34b-pp12-serve-chat")
    model = cell["model"]["family"].program_model(cell["model"])
    geo = cell["job"]["serve_config"]
    s, bs = geo["slots"], geo["block_size"]
    t_cap = geo["max_len"] // bs * bs
    state = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(lambda: paged_kv.init_paged_state(model, s)))
    store = sum(x.size * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(state))
    assert 1.6e9 < store < 1.63e9
    take, admit, first = paged_kv._boundary_programs(
        "compile-test", 0.0, 0, 1.0, model, bs, geo["max_len"] // bs)
    tokens, pos = spec((s, t_cap), jnp.int32), spec((s,), jnp.int32)
    where = spec((3,), jnp.int32)
    stats = {"ssm": spec((len(paged_kv.SSM_COUNTERS),), jnp.int32)}

    compiled = admit.lower(tokens, pos, state, spec((t_cap,), jnp.int32),
                           where).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes > store
    assert mem.temp_size_in_bytes < 64 << 20, mem.temp_size_in_bytes
    moved = [line.strip()[:160] for line in compiled.as_text().splitlines()
             if re.search(r"= \S*\[64,32,128,256\]\S* (?:copy|copy-start)\(",
                          line)]
    assert not moved, moved

    logits = spec((1, cell["model"]["vocab_size"]), jnp.float32)
    compiled = first.lower(logits, tokens, pos, spec((2,), jnp.uint32),
                           spec((2,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 8 << 20, mem.temp_size_in_bytes
    assert mem.output_size_in_bytes - mem.alias_size_in_bytes < 1 << 20

    compiled = take.lower(tokens, stats, spec((), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 0       # buffers of their own
    assert mem.output_size_in_bytes < 1 << 20
    assert mem.temp_size_in_bytes < 1 << 20


# --- the data-parallel train step over the four described chips --------------


def _entry_all_reduces(compiled_text: str):
    """``(in line, beside a product)``: the byte sizes of the all-reduces
    the entry computation runs as instructions of their own, and the number
    it runs as ``async-collective-start`` fusions (the form in which this
    compiler puts a collective inside a product; the ``-done`` half is not
    counted)."""
    import re

    entry = compiled_text[compiled_text.index("\nENTRY "):]
    in_line = []
    for shape in re.findall(r"= (\S+) all-reduce\(", entry):
        dims = re.match(r"f32\[([\d,]*)\]", shape)
        n = 1
        for d in (dims.group(1).split(",") if dims and dims.group(1) else []):
            n *= int(d)
        in_line.append(4 * n if dims else 0)   # a tuple: the vectors' one
    fused = len(re.findall(r"%async-collective-start\S* = .* fusion\(",
                           entry))
    return in_line, fused


@pytest.mark.parametrize("with_options", [False, True])
def test_dp4_step_all_reduces_beside_the_products(topo, with_options):
    """``gpt2m-train-dp4``'s step (gpt2-medium's widths at 2 layers, AdamW,
    ``--ce_chunk 256``, the flash kernels, 4 sequences a chip) on a data
    mesh of the four described chips.  As the compiler takes it, the
    combiner merges the leaves into a few all-reduces that run in line
    with nothing beside them; under ``dp.exchange_overlap_options`` every
    matrix keeps an all-reduce of its own and most of them run inside
    weight-gradient products.  In line stay the leaves whose gradients are
    made last and the one all-reduce the vectors and scalars share
    (PERF.md section 6, PR 34)."""
    from unittest import mock

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from neural_networks_parallel_training_with_mpi_tpu.ops import optim
    from neural_networks_parallel_training_with_mpi_tpu.parallel import (
        data_parallel as dp,
    )
    from neural_networks_parallel_training_with_mpi_tpu.train.state import (
        TrainState,
    )

    model = Transformer(TransformerConfig(
        vocab_size=50257, max_seq_len=1024, n_layers=2, d_model=1024,
        n_heads=16, d_ff=4096, pos_encoding="learned", ce_chunk=256,
        attention="auto", param_dtype=jnp.float32, compute_dtype=BF16))
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), dp.DATA_AXES)
    options = dp.exchange_overlap_options(mesh)
    assert options    # a TPU data mesh of four
    opt = optim.adamw(3e-4, weight_decay=0.1)
    step = dp.make_train_step(
        model, opt, mesh, loss_name="cross_entropy",
        compiler_options=options if with_options else None)
    params = jax.eval_shape(lambda: model.init(prng.init_key(0)))

    def placed(tree, sharding):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding), tree)

    whole, rows = NamedSharding(mesh, P()), NamedSharding(mesh,
                                                          P(dp.DATA_AXES))
    state = placed(TrainState(step=jax.ShapeDtypeStruct((), jnp.int32),
                              params=params,
                              opt_state=jax.eval_shape(opt.init, params),
                              qstate=()), whole)
    batch = placed({"x": jax.ShapeDtypeStruct((16, 1024), jnp.int32),
                    "y": jax.ShapeDtypeStruct((16, 1024), jnp.int32),
                    "mask": jax.ShapeDtypeStruct((16,), jnp.float32)}, rows)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = step.lower(state, batch)
    assert "flash_bwd_dkv" in lowered.as_text()
    in_line, fused = _entry_all_reduces(lowered.compile().as_text())
    # 2 layers x (qkv, attn_out, ff_in, ff_out) + table, positions, head
    matrices = [x for x in jax.tree_util.tree_leaves(params)
                if 4 * x.size >= dp.ALL_REDUCE_COMBINE_BYTES]
    assert len(matrices) == 11
    if not with_options:
        assert fused == 0 and len(in_line) <= 4, (in_line, fused)
        return
    big = [b for b in in_line if b >= dp.ALL_REDUCE_COMBINE_BYTES]
    assert fused >= 6, (in_line, fused)
    assert len(big) + fused == len(matrices), (in_line, fused)
    assert len(in_line) - len(big) <= 1, in_line
