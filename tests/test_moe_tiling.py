"""The tiles of the expert layer's grouped product (``models/moe.py``
``gmm_tiling``) at the three expert shapes the serving cells compile, and
the kernel under the whole-expert tiling against ``jax.lax.ragged_dot``.

Shapes: (model width, expert width, top-k, decode slots); the prefill
buckets of a 1024-column chunk are 8 to 1024 columns.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neural_networks_parallel_training_with_mpi_tpu.models import moe
from neural_networks_parallel_training_with_mpi_tpu.serve.paged_kv import (
    prefill_bucket,
)
from neural_networks_parallel_training_with_mpi_tpu.utils import (
    compile_ledger,
)

SPLIT = {"ms4": (4096, 2048, 4, 32),        # mistral-small-4-119b-ep4
         "kexaone": (6144, 2048, 8, 48)}    # k-exaone-236b-ep8
MELLUM2 = (2304, 896, 8, 64)                # mellum2-12b-pp4
BUCKETS = sorted({prefill_bucket(w) for w in range(1, 1025)})


def programs(d, f, k, slots):
    """(name, m, k, n) of gate / up and of down, in the decode tick and in
    each prefill bucket."""
    for name, tokens in [("tick", slots)] + [(f"chunk{b}", b)
                                            for b in BUCKETS]:
        m = tokens * k
        yield name + ".in", m, d, f
        yield name + ".out", m, f, d


def parent_tiling(m, k, n):
    """The rule every split-weight shape keeps: rows from ``m`` alone, the
    weight tile capped at 2048 x 1024."""
    tm = 256 if m >= 4096 else (128 if m >= 512 else 16)
    while m % tm:
        tm //= 2
    return tm, min(k, 2048), min(n, 1024)


@pytest.mark.parametrize("cfg", sorted(SPLIT))
@pytest.mark.parametrize("product,tick,chunk", [
    ("in", (16, 2048, 1024), (256, 2048, 1024)),
    ("out", (16, 2048, 1024), (256, 2048, 1024)),
])
def test_split_weights_keep_the_parents_tiles(cfg, product, tick, chunk):
    """``ms4``'s and ``kexaone``'s tick and 1024-column chunk get the tiles
    they were timed at, and every other bucket the parent's rule."""
    d, f, k, slots = SPLIT[cfg]
    kk, nn = (d, f) if product == "in" else (f, d)
    assert moe.gmm_tiling(slots * k, kk, nn) == tick
    assert moe.gmm_tiling(1024 * k, kk, nn) == chunk
    for name, m, kk, nn in programs(*SPLIT[cfg]):
        assert moe.gmm_tiling(m, kk, nn) == parent_tiling(m, kk, nn), name


@pytest.mark.parametrize("tokens,tm", [(64, 128), (1024, 256)],
                         ids=["tick", "chunk1024"])
def test_whole_expert_tiles_at_the_timed_row_tiles(tokens, tm):
    """Mellum2's 2304 x 896 fits the VMEM of the 2048 x 1024 tile, so the
    tile is the whole expert; the row tile is the one the chip timed
    fastest: 128 for the tick's 512 rows (8 an expert: 16-row tiles cost
    more visits than their padding saves), 256 for the chunk's 8192."""
    d, f, k, _ = MELLUM2
    assert moe.gmm_tiling(tokens * k, d, f) == (tm, d, f)
    assert moe.gmm_tiling(tokens * k, f, d) == (tm, f, d)


@pytest.mark.parametrize("cfg", ["ms4", "kexaone", "mellum2"])
def test_no_product_runs_a_remainder_tile(cfg):
    shape = MELLUM2 if cfg == "mellum2" else SPLIT[cfg]
    for name, m, k, n in programs(*shape):
        tm, tk, tn = moe.gmm_tiling(m, k, n)
        assert m % tm == 0 and k % tk == 0 and n % tn == 0, name
        assert 16 <= tm <= 256 and tk * tn <= 2048 * 1024, name


@pytest.mark.parametrize("dim,cap,tile", [
    (2304, 2048, 1152), (2304, 1024, 768), (6144, 1024, 1024),
    (896, 1024, 896), (2100, 2048, 2048),
])
def test_a_split_tile_divides_its_dimension(dim, cap, tile):
    """A dimension the cap does not divide gets the largest multiple of 128
    under the cap that does; one no such multiple divides keeps the cap."""
    assert moe._dividing_tile(dim, cap) == tile


def test_kernel_under_whole_expert_tiles_agrees_with_ragged_dot():
    """The Pallas grouped matmul (interpreted on the CPU) at the tiling the
    rule gives a whole-expert shape, over uneven groups, an empty one and
    rows past the groups, gives ``ragged_dot``'s rows."""
    g, k, n = 4, 256, 128
    sizes = jnp.array([23, 0, 30, 7], jnp.int32)      # 60 of 64 rows
    m = 64
    assert moe.gmm_tiling(m, k, n) == (16, k, n)
    kx, kw = jax.random.split(jax.random.PRNGKey(3))
    xs = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (g, k, n), jnp.float32)
    got = moe.grouped_matmul(xs, w, sizes, impl="gmm")
    want = moe.grouped_matmul(xs, w, sizes, impl="ragged")
    rows = int(sizes.sum())
    np.testing.assert_allclose(np.asarray(got[:rows]),
                               np.asarray(want[:rows]), rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("impl", ["gmm", "ragged"])
def test_the_layer_notes_its_tiles_on_the_programs_event(impl):
    """``DroplessMoE`` says what implements its grouped products on the
    compile-ledger event of the program being lowered, once, with the
    kernel's tiles of gate / up and of down."""
    layer = moe.DroplessMoE(d_model=32, d_ff=64, n_experts=4, top_k=2,
                            impl=impl)
    params = layer.init(jax.random.PRNGKey(0))
    y = jnp.ones((3, 16, 32), jnp.float32)
    ledger = compile_ledger.Ledger(None)
    compile_ledger.install(ledger)
    try:
        with ledger.collecting_notes() as notes:
            jax.jit(lambda p, y: layer.apply(p, y)[0]).lower(params, y)
    finally:
        compile_ledger.install(None)
    if impl == "ragged":
        assert notes == {"experts": {"impl": "ragged"}}
    else:
        # 48 tokens x top-2 = 96 rows
        assert notes == {"experts": {"impl": "gmm", "tiling": {
            "in": [16, 32, 64], "out": [16, 64, 32]}}}
