"""Fused paged attention: the Pallas kernel family
(``ops.pallas_kernels.paged_attention``) and its serving dispatch seam
(``serve/paged_kv.py``, ``attn_impl='fused'``).

Three layers of pins:

* **kernel vs. plain-numpy reference** — decode (width 1), chunked
  prefill (width > 1, per-row causal), GQA head folding, int8
  dequant-on-load, and the inactive-lane (``length 0``) zero-output
  convention, all in interpret mode on CPU (the ``_interpret_default``
  seam — CPU lanes never need a flag).
* **fused == gathered tokens** — the serving contract: swapping the
  attention dispatch must not move a single token.  The gathered path is
  pinned against dense ``DecodeServer``/``generate()`` by
  tests/test_serve_paged.py, so these pins chain the fused kernel to the
  eager reference without re-paying it.
* **the recompile invariant** — block tables and lengths are traced
  scalar-prefetch operands: admission, growth, eviction and re-admission
  re-run ONE compiled step program (``_cache_size`` pinned).

Core-lane budget note: one pinned-geometry parity scenario (plus the
cheap kernel-reference pins) runs in the budgeted core lane; per-variant
fresh compiles (GQA / int8 / scan_layers / rope) are in the slow lane,
and random-geometry scheduler fuzz under the fused path rides the
``serve`` lane in tests/test_serve_sched.py."""

import jax.numpy as jnp
import numpy as np
import pytest

from neural_networks_parallel_training_with_mpi_tpu.models.transformer import (
    Transformer, TransformerConfig,
)
from neural_networks_parallel_training_with_mpi_tpu.ops.pallas_kernels import (
    paged_attention,
)
from neural_networks_parallel_training_with_mpi_tpu.serve import (
    PagedDecodeServer,
)
from neural_networks_parallel_training_with_mpi_tpu.utils import prng

pytestmark = pytest.mark.pallas

VOCAB = 64


def _model(**kw):
    base = dict(vocab_size=VOCAB, max_seq_len=64, n_layers=2, d_model=32,
                n_heads=4, d_ff=64)
    base.update(kw)
    return Transformer(TransformerConfig(**base))


def _drain(srv, rid, prefill_width=16):
    while not srv.prefill_step(rid, prefill_width):
        pass
    while not srv.done(rid):
        srv.step()
    return srv.result(rid)


# ---------------------------------------------------------------------------
# kernel vs. plain-numpy reference
# ---------------------------------------------------------------------------

def _np_reference(q, kp, vp, tables, lens, starts, ks=None, vs=None,
                  window=None):
    """The paged-attention math in plain numpy: gather each stream's live
    blocks, truncate to its true length, per-row causal softmax (over the
    ``window`` keys up to the row's own where one is given)."""
    s_n, w, n_heads, hd = q.shape
    _, bs, kv_heads, _ = kp.shape
    g = n_heads // kv_heads
    out = np.zeros((s_n, w, n_heads, hd), np.float32)
    for s in range(s_n):
        ln = int(lens[s])
        if ln == 0:
            continue
        nb = -(-ln // bs)
        gat = lambda pool: np.concatenate(                 # noqa: E731
            [np.asarray(pool, np.float32)[tables[s, j]] for j in range(nb)],
            axis=0)[:ln]
        k, v = gat(kp), gat(vp)
        if ks is not None:
            k = k * gat(ks)[..., None]
            v = v * gat(vs)[..., None]
        for col in range(w):
            q_pos = int(starts[s]) + col
            for h in range(n_heads):
                c = h // g
                sc = (np.asarray(q, np.float32)[s, col, h]
                      @ k[:, c].T) / np.sqrt(hd)
                seen = np.arange(ln) <= q_pos
                if window is not None:
                    seen &= np.arange(ln) > q_pos - window
                sc = np.where(seen, sc, -1e30)
                p = np.exp(sc - sc.max())
                p /= p.sum()
                out[s, col, h] = p @ v[:, c]
    return out


def _pool_fixture(seed=0, nb=10, bs=4, kv=2, hd=8):
    rng = np.random.default_rng(seed)
    kp = jnp.asarray(rng.normal(size=(nb, bs, kv, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, bs, kv, hd)), jnp.float32)
    tables = np.zeros((3, 5), np.int32)
    tables[0, :3] = [1, 4, 7]
    tables[1, :2] = [2, 9]
    tables[2, :1] = [5]
    return rng, kp, vp, tables


def test_kernel_decode_matches_reference():
    """Width-1 (decode) against the numpy reference: ragged lengths, a
    block-straddling stream, and an INACTIVE length-0 lane that must
    contribute exactly nothing (output 0, zero blocks walked)."""
    rng, kp, vp, tables = _pool_fixture()
    lens = np.asarray([11, 6, 0], np.int32)
    starts = np.maximum(lens - 1, 0).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(3, 1, 4, 8)), jnp.float32)
    got = paged_attention(q, kp, vp, jnp.asarray(tables),
                          jnp.asarray(lens), jnp.asarray(starts))
    want = _np_reference(q, kp, vp, tables, lens, starts)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-6)
    assert np.all(np.asarray(got)[2] == 0.0)      # inactive lane: nothing


def test_kernel_prefill_chunk_causal_gqa():
    """Width-4 chunk (the prefill variant) at nonzero start positions:
    per-row causal masking against absolute positions, with GQA folding
    (4 query heads over 2 kv heads)."""
    rng, kp, vp, tables = _pool_fixture(seed=1)
    lens = np.asarray([11, 6, 4], np.int32)
    starts = np.asarray([7, 2, 0], np.int32)
    q = jnp.asarray(rng.normal(size=(3, 4, 4, 8)), jnp.float32)
    got = paged_attention(q, kp, vp, jnp.asarray(tables),
                          jnp.asarray(lens), jnp.asarray(starts))
    want = _np_reference(q, kp, vp, tables, lens, starts)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-6)


def test_kernel_int8_dequant_on_load():
    """int8 pools with per-(position, head) f32 scales dequantize inside
    the kernel — same numbers as dequantizing before the reference."""
    rng, _, _, tables = _pool_fixture(seed=2)
    kq = rng.integers(-127, 127, (10, 4, 2, 8)).astype(np.int8)
    vq = rng.integers(-127, 127, (10, 4, 2, 8)).astype(np.int8)
    ks = rng.uniform(0.01, 0.1, (10, 4, 2)).astype(np.float32)
    vs = rng.uniform(0.01, 0.1, (10, 4, 2)).astype(np.float32)
    lens = np.asarray([9, 3, 12], np.int32)
    tables[2, :3] = [3, 6, 8]
    starts = np.maximum(lens - 1, 0).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(3, 1, 4, 8)), jnp.float32)
    got = paged_attention(q, jnp.asarray(kq), jnp.asarray(vq),
                          jnp.asarray(tables), jnp.asarray(lens),
                          jnp.asarray(starts), k_scale=jnp.asarray(ks),
                          v_scale=jnp.asarray(vs))
    want = _np_reference(q, kq.astype(np.float32), vq.astype(np.float32),
                         tables, lens, starts, ks, vs)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


def _scattered_tables(rng, lens, nb, bs, mb):
    """Each stream's live pages drawn without order from the pool (never
    the sink); the table's other entries stay at the sink."""
    tables = np.zeros((len(lens), mb), np.int32)
    free = list(rng.permutation(np.arange(1, nb)))
    for i, ln in enumerate(lens):
        for j in range(-(-int(ln) // bs)):
            tables[i, j] = free.pop()
    return tables


# (lens, starts or None for decode, width, heads, kv, hd, bs, mb, pages,
#  tile_cols): what the walk of several pages a step and the row tiles
# must get right
_WALKS = {
    # decode over lengths that are no multiple of pages * block_size (3 x 4
    # = 12 keys a step): tails of 1, 5 and 11 keys, and one exact multiple
    "decode-ragged-tails": ([13, 29, 35, 24], None, 1, 4, 2, 8, 4, 10, 3,
                            None),
    # one page, and less than one page, where a step holds eight
    "decode-one-page": ([4, 3, 1], None, 1, 4, 2, 8, 4, 6, 8, None),
    # idle lanes between live ones walk nothing and write zeros
    "decode-idle-between": ([0, 9, 0, 0, 17, 0], None, 1, 4, 4, 8, 4, 6, 2,
                            None),
    # one page a step: the walk the int8 pools take
    "decode-page-a-step": ([11, 6, 22], None, 1, 4, 2, 8, 4, 6, 1, None),
    # a chunk in four row tiles at a start that is no multiple of the
    # page, of the step or of the tile: rows cross all three borders
    "chunk-tiles-cross-pages": ([16 + 21], [21], 16, 4, 2, 8, 4, 12, 2, 4),
    # a bucketed chunk: 16 columns of which 9 are real (the rest lie past
    # ``len``: whole pad tiles walk nothing)
    "chunk-pad-columns": ([6 + 9], [6], 16, 4, 2, 8, 4, 8, 2, 4),
    # two streams' chunks side by side, one at position 0
    "chunk-two-streams": ([8, 19], [0, 11], 8, 6, 2, 8, 4, 8, 4, 4),
}


@pytest.mark.parametrize("case", sorted(_WALKS))
def test_kernel_walks_pages_and_row_tiles(case):
    """The kernel against the numpy reference where the multi-page walk
    and the row tiles can go wrong, over tables whose live pages are
    scattered over the pool; the pool's sink block holds huge values, so a
    key past a stream's length that reached a softmax would show."""
    lens, starts, w, heads, kv, hd, bs, mb, pages, cols = _WALKS[case]
    rng = np.random.default_rng(len(case))
    nb = 1 + sum(-(-ln // bs) for ln in lens) + 3
    kp = rng.normal(size=(nb, bs, kv, hd)).astype(np.float32)
    vp = rng.normal(size=(nb, bs, kv, hd)).astype(np.float32)
    kp[0], vp[0] = 1e4, 1e4                       # the sink: never attended
    lens = np.asarray(lens, np.int32)
    starts = (np.maximum(lens - 1, 0) if starts is None
              else np.asarray(starts)).astype(np.int32)
    tables = _scattered_tables(rng, lens, nb, bs, mb)
    q = jnp.asarray(rng.normal(size=(len(lens), w, heads, hd)), jnp.float32)
    got = np.asarray(paged_attention(
        q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables),
        jnp.asarray(lens), jnp.asarray(starts), pages=pages, tile_cols=cols))
    want = _np_reference(q, kp, vp, tables, lens, starts)
    # rows at or past ``len`` (pad columns) are the caller's to discard:
    # a tile that holds a live row computes them, a tile of pad rows alone
    # writes zeros
    live = (starts[:, None] + np.arange(w)[None, :]) < lens[:, None]
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-6)
    assert np.isfinite(got).all()
    assert np.all(got[lens == 0] == 0.0)
    # the folded pool, the layout the fused server stores, reads the same
    folded = np.asarray(paged_attention(
        q, jnp.asarray(kp.reshape(nb, bs, kv * hd)),
        jnp.asarray(vp.reshape(nb, bs, kv * hd)), jnp.asarray(tables),
        jnp.asarray(lens), jnp.asarray(starts), pages=pages, tile_cols=cols))
    np.testing.assert_array_equal(folded, got)


# (lens, starts or None for decode, width, window, pages, tile_cols) at 4
# heads over 2 KV heads of 8 lanes and pages of 4: lengths below, at and
# above the window, bounds inside a page and on its edge
_WINDOWS = {
    # decode: shorter than the window, exactly it, one more, many windows,
    # and a bound that falls on a page's first and last position
    "decode-around-the-window": ([3, 8, 9, 40, 12, 15, 0, 33], None, 1, 8,
                                 None, None),
    # the same with a window that is no multiple of the page, in steps of
    # two pages: the walk takes several masked steps
    "decode-odd-window-short-steps": ([5, 7, 8, 23, 38], None, 1, 7, 2,
                                      None),
    # a window inside one page
    "decode-window-in-a-page": ([2, 3, 4, 17], None, 1, 3, None, None),
    # a chunk longer than the window, at a start that is no multiple of
    # the page, in four row tiles: every tile starts its own walk
    "chunk-longer-than-the-window": ([21 + 16], [21], 16, 8, None, 4),
    # a chunk at position 0 (the bound clamps at the stream's first page)
    # beside one far into its stream; pad columns past ``len``
    "chunk-two-streams-pad": ([9, 30 + 11], [0, 30], 16, 8, 2, 4),
    # one tile for the whole chunk, a window wider than the chunk
    "chunk-inside-the-window": ([13 + 8], [13], 8, 12, None, 8),
}


@pytest.mark.parametrize("case", sorted(_WINDOWS))
def test_kernel_with_a_window_walks_from_its_bound(case):
    """The kernel with ``window`` against the numpy reference and against
    the gathered reduction's mask.  Table entries before the first page a
    stream's rows can see point at the SINK (as the window kind's allocator
    leaves them), whose rows hold huge values: a walk that began too early,
    or a first page not masked by position, would show."""
    lens, starts, w, window, pages, cols = _WINDOWS[case]
    heads, kv, hd, bs, mb = 4, 2, 8, 4, 12
    rng = np.random.default_rng(len(case))
    nb = 1 + sum(-(-ln // bs) for ln in lens) + 3
    kp = rng.normal(size=(nb, bs, kv, hd)).astype(np.float32)
    vp = rng.normal(size=(nb, bs, kv, hd)).astype(np.float32)
    lens = np.asarray(lens, np.int32)
    starts = (np.maximum(lens - 1, 0) if starts is None
              else np.asarray(starts)).astype(np.int32)
    tables = _scattered_tables(rng, lens, nb, bs, mb)
    q = jnp.asarray(rng.normal(size=(len(lens), w, heads, hd)), jnp.float32)
    want = _np_reference(q, kp, vp, tables, lens, starts, window=window)
    # behind the window: released, the table back at the sink
    behind = np.maximum(starts - window + 1, 0) // bs
    for i, n in enumerate(behind):
        tables[i, :n] = 0
    kp[0], vp[0] = 1e4, 1e4
    got = np.asarray(paged_attention(
        q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables),
        jnp.asarray(lens), jnp.asarray(starts), pages=pages, tile_cols=cols,
        window=window))
    live = np.arange(w)[None, :] < (lens - starts)[:, None]     # (S, W)
    # (pad columns past ``len`` are the caller's to discard)
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-5)
    # a window at least as long as every stream is the walk without one
    if window >= lens.max():
        plain = np.asarray(paged_attention(
            q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables),
            jnp.asarray(lens), jnp.asarray(starts)))
        np.testing.assert_allclose(got[live], plain[live], rtol=2e-5,
                                   atol=2e-5)


def test_window_walks_have_rows_of_their_own_in_the_tile_table():
    from neural_networks_parallel_training_with_mpi_tpu.ops import (
        pallas_kernels,
    )

    tiles = pallas_kernels.paged_tiles
    # untimed: one step holds what a tile's rows can see (window 128 at
    # pages of 16: 8 pages and the one the bound cuts)
    assert tiles(16, 512, 1, 8, 1088, window=128) == (9, 1)
    assert tiles(8, 32, 16, 2, 64, window=8, tile_cols=4) == (3, 4)
    assert tiles(8, 32, 16, 1, 64, window=8, tile_cols=4) == (4, 16)
    # the cell's row was timed for both walks (PERF.md section 6, PR 33)
    for kind in ("decode", "chunk", "decode_window", "chunk_window"):
        assert (16, 1024, kind) in pallas_kernels.PAGED_TILES, kind
    with pytest.raises(ValueError, match="window"):
        paged_attention(jnp.zeros((1, 1, 2, 8)), jnp.zeros((3, 4, 2, 8)),
                        jnp.zeros((3, 4, 2, 8)), jnp.zeros((1, 2), jnp.int32),
                        jnp.ones((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
                        window=0)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("width", [1, 512], ids=["decode", "chunk512"])
def test_kernel_at_the_cells_heads(width, dtype, tol):
    """``starcoder2-3b``'s heads (24 over 2 KV heads of 128, blocks of 16)
    at the tiling the timed table gives them: a decode step over four
    streams, and a 512-wide chunk whose 32-column row tiles cross page and
    step borders (start 37).  f32 pools to the f32 tolerance; bf16 pools at
    the tolerance the flash kernels' bf16 tests use, against the reference
    on the same bf16 values."""
    from neural_networks_parallel_training_with_mpi_tpu.ops.pallas_kernels import (  # noqa: E501
        PAGED_TILES, paged_tiles,
    )

    heads, kv, hd, bs, mb = 24, 2, 128, 16, 64
    kind = "decode" if width == 1 else "chunk"
    assert paged_tiles(bs, kv * hd, width, heads // kv, mb) \
        == PAGED_TILES[(bs, kv * hd, kind)]
    rng = np.random.default_rng(width)
    if width == 1:
        lens = np.asarray([300, 0, 17, 513], np.int32)
        starts = np.maximum(lens - 1, 0).astype(np.int32)
    else:
        starts = np.asarray([37], np.int32)
        lens = starts + 300                       # 300 real columns of 512
    nb = 1 + int(sum(-(-int(ln) // bs) for ln in lens)) + 2
    kp = jnp.asarray(rng.normal(size=(nb, bs, kv, hd)), dtype)
    vp = jnp.asarray(rng.normal(size=(nb, bs, kv, hd)), dtype)
    tables = _scattered_tables(rng, lens, nb, bs, mb)
    q = jnp.asarray(rng.normal(size=(len(lens), width, heads, hd)), dtype)
    got = paged_attention(q, kp.reshape(nb, bs, kv * hd),
                          vp.reshape(nb, bs, kv * hd), jnp.asarray(tables),
                          jnp.asarray(lens), jnp.asarray(starts))
    assert got.dtype == jnp.dtype(dtype)
    got = np.asarray(got, np.float32)
    want = _np_reference(np.asarray(q, np.float32),
                         np.asarray(kp, np.float32),
                         np.asarray(vp, np.float32), tables, lens, starts)
    live = (starts[:, None] + np.arange(width)[None, :]) < lens[:, None]
    np.testing.assert_allclose(got[live], want[live], rtol=tol, atol=tol)
    assert np.isfinite(got).all()


def test_kernel_feeds_the_mxu_in_the_pools_type():
    """bf16 pools: every product inside the kernel takes bf16 operands and
    accumulates in f32 (no upcast before the MXU); f32 pools stay f32
    products, and int8 pools keep their f32 scheme."""
    import jax

    def dots(dtype, scales=False):
        q = jnp.zeros((2, 1, 4, 8), jnp.float32 if scales else dtype)
        pool = jnp.zeros((6, 4, 2, 8), dtype)
        sc = jnp.ones((6, 4, 2), jnp.float32) if scales else None
        tables = jnp.zeros((2, 3), jnp.int32)
        lens = jnp.ones((2,), jnp.int32)

        def fn(q_, kp, vp):
            return paged_attention(q_, kp, vp, tables, lens, lens - 1,
                                   k_scale=sc, v_scale=sc)

        found = []

        def walk(jaxpr, inside):
            for eqn in jaxpr.eqns:
                kernel = inside or eqn.primitive.name == "pallas_call"
                if inside and eqn.primitive.name == "dot_general":
                    found.append((tuple(v.aval.dtype for v in eqn.invars),
                                  eqn.params["preferred_element_type"]))
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub, kernel)

        walk(jax.make_jaxpr(fn)(q, pool, pool).jaxpr, False)
        return found

    bf16, f32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
    found = dots(jnp.bfloat16)
    # scores and values, per KV head, in the unmasked and the masked loop
    assert len(found) == 2 * 2 * 2
    assert {d for d, _ in found} == {(bf16, bf16)}
    assert {acc for _, acc in found} == {f32}
    assert {d for d, _ in dots(jnp.float32)} == {(f32, f32)}
    assert {d for d, _ in dots(jnp.int8, scales=True)} == {(f32, f32)}


def test_kernel_validates_shapes():
    rng, kp, vp, tables = _pool_fixture()
    lens = jnp.zeros((3,), jnp.int32)
    q = jnp.zeros((3, 1, 3, 8), jnp.float32)      # 3 heads over 2 kv
    with pytest.raises(ValueError):
        paged_attention(q, kp, vp, jnp.asarray(tables), lens, lens)
    q = jnp.zeros((3, 1, 4, 8), jnp.float32)
    with pytest.raises(ValueError):               # one scale, not both
        paged_attention(q, kp, vp, jnp.asarray(tables), lens, lens,
                        k_scale=jnp.ones((10, 4, 2)))


# ---------------------------------------------------------------------------
# the shared-row mode: one pool row is key and value (latent attention's
# absorbed decode), against ``attend_absorbed`` over gathered rows
# ---------------------------------------------------------------------------

def _latent_attn():
    from neural_networks_parallel_training_with_mpi_tpu.models.mla import (
        LatentAttention,
    )
    from neural_networks_parallel_training_with_mpi_tpu.ops.rope import (
        RopeScaling,
    )

    return LatentAttention(
        d_model=48, n_heads=4, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=12,
        rope_scaling=RopeScaling(4.0, 16, 4.0, 1.0, 1.0, 1.0, 0.1))


# (lens, block_size, max_blocks, pages): lengths that end inside a page and
# on its border, idle lanes between live ones, the table's full width, and
# one page a step
_SHARED_ROW = {
    "ragged-inside-pages": ([13, 29, 35, 24], 4, 10, 3),
    "idle-between": ([0, 9, 0, 17, 0], 4, 6, 2),
    "table-full-width": ([40, 40, 37], 4, 10, 4),
    "one-page-a-step": ([11, 6, 22], 8, 3, 1),
    "less-than-a-page": ([3, 1], 8, 4, 8),
}


@pytest.mark.parametrize("case", sorted(_SHARED_ROW))
def test_shared_row_mode_is_the_absorbed_form_over_the_pool(case):
    """``v_pool=None``: the kernel between the two ``mla_absorb`` products
    against ``LatentAttention.attend_absorbed`` over ``pool[tables]``, the
    row stored padded to 128 lanes.  Every position no stream holds (the
    sink, the tail of a stream's last page, whole pages past its length
    that its table still names) holds huge non-zero values: a key past a
    length that reached a softmax, or a value row past the last fetched
    key, would show.  A lane of length 0 walks nothing and reads 0."""
    lens, bs, mb, pages = _SHARED_ROW[case]
    attn = _latent_attn()
    ap = attn.init(prng.init_key(3))
    rng = np.random.default_rng(len(case))
    lens = np.asarray(lens, np.int32)
    b, t_cap, lanes = len(lens), bs * mb, 128
    nb = 1 + b * mb
    pool = np.full((nb, bs, lanes), 1e4, np.float32)
    tables = np.arange(1, nb, dtype=np.int32).reshape(b, mb)
    tables = rng.permutation(tables.reshape(-1)).reshape(b, mb)
    rows = rng.normal(size=(b, t_cap, attn.row_dim)).astype(np.float32)
    for i, ln in enumerate(lens):
        for t in range(int(ln)):
            pool[tables[i, t // bs], t % bs, :attn.row_dim] = rows[i, t]
            pool[tables[i, t // bs], t % bs, attn.row_dim:] = 0.0
    q_nope = jnp.asarray(rng.normal(size=(b, 1, 4, 8)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(b, 1, 4, 8)), jnp.float32)
    # the reference: the gathered rows (garbage and all) under the mask
    got_rows = jnp.asarray(pool)[jnp.asarray(tables)].reshape(
        b, t_cap, lanes)[..., :attn.row_dim]
    pos = jnp.asarray(lens - 1)[:, None]
    mask = jnp.arange(t_cap)[None, None, :] <= pos[:, :, None]
    want = np.asarray(attn.attend_absorbed(ap, q_nope, q_rope, got_rows,
                                           mask))
    w_k, w_v = attn.absorb_weights(ap)
    q_row = jnp.pad(jnp.concatenate(
        [attn.absorb_query(w_k, q_nope), q_rope], axis=-1),
        [(0, 0)] * 3 + [(0, lanes - attn.row_dim)])
    u = paged_attention(q_row, jnp.asarray(pool), None, jnp.asarray(tables),
                        jnp.asarray(lens), jnp.asarray(lens - 1),
                        v_lanes=attn.kv_lora_rank, scale=attn.softmax_scale,
                        pages=pages)
    assert u.shape == (b, 1, 4, attn.kv_lora_rank)
    got = np.asarray(attn.absorb_value(w_v, u))
    live = lens > 0
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-6)
    assert np.isfinite(got).all() and np.all(got[~live] == 0.0)


def test_shared_row_mode_feeds_the_mxu_in_the_pools_type_and_validates():
    """bf16 rows: one fetch a page (no value pool among the operands), the
    two products of a step take bf16 operands and accumulate in f32; the
    mode needs ``v_lanes``, the query as wide as the row, and no scales."""
    import jax

    pool = jnp.zeros((6, 8, 128), jnp.bfloat16)
    q = jnp.zeros((2, 1, 4, 128), jnp.bfloat16)
    tables = jnp.zeros((2, 3), jnp.int32)
    lens = jnp.ones((2,), jnp.int32)
    fn = lambda q_, p_: paged_attention(                        # noqa: E731
        q_, p_, None, tables, lens, lens - 1, v_lanes=16, scale=0.3)
    found, pools_in = [], []

    def walk(jaxpr, inside):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                pools_in.append(sum(v.aval.shape == pool.shape
                                    for v in eqn.invars))
            kernel = inside or eqn.primitive.name == "pallas_call"
            if inside and eqn.primitive.name == "dot_general":
                found.append((tuple(v.aval.dtype for v in eqn.invars),
                              eqn.params["preferred_element_type"]))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, kernel)

    walk(jax.make_jaxpr(fn)(q, pool).jaxpr, False)
    bf16, f32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
    assert pools_in == [1]
    assert len(found) == 2 * 2          # scores and values, two loops
    assert {d for d, _ in found} == {(bf16, bf16)}
    assert {acc for _, acc in found} == {f32}
    with pytest.raises(ValueError, match="needs v_lanes"):
        paged_attention(q, pool, None, tables, lens, lens - 1)
    with pytest.raises(ValueError, match="must be the pool row's"):
        paged_attention(q[..., :64], pool, None, tables, lens, lens - 1,
                        v_lanes=16)
    with pytest.raises(ValueError, match="shared-row mode"):
        paged_attention(q, pool, pool, tables, lens, lens - 1, v_lanes=16)


# ---------------------------------------------------------------------------
# fused == gathered through the serving surface (the token contract)
# ---------------------------------------------------------------------------

def _staggered_scenario(srv):
    """Staggered ragged admissions incl. an 11-token prompt prefilled in
    width-4 chunks straddling the 8-position block boundary — the
    gathered parity suite's scenario, reused verbatim."""
    reqs = []
    a = srv.try_admit([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11], 12)
    while not srv.prefill_step(a, 4):
        pass
    reqs.append(a)
    srv.step(); srv.step()
    b = srv.try_admit([7, 8], 6)
    while not srv.prefill_step(b, 16):
        pass
    reqs.append(b)
    srv.step()
    c = srv.try_admit([5, 9, 11, 13], 9)
    while not srv.prefill_step(c, 16):
        pass
    reqs.append(c)
    for _ in range(40):
        srv.step()
        if all(srv.done(r) for r in reqs):
            break
    out = [srv.result(r) for r in reqs]
    srv.allocator.assert_drained()                # no leak on the kernel path
    return out


def test_fused_matches_gathered_staggered_straddling():
    """The core-lane parity pin: same staggered block-straddling scenario
    through both attention impls — token-identical, allocator drained.
    (gathered == dense DecodeServer == generate() is pinned by
    tests/test_serve_paged.py, so this chains fused to the reference.)"""
    model = _model()
    params = model.init(prng.init_key(0))
    outs = {}
    for impl in ("gathered", "fused"):
        srv = PagedDecodeServer(model, params, slots=4, num_blocks=40,
                                block_size=8, attn_impl=impl)
        outs[impl] = _staggered_scenario(srv)
    assert outs["fused"] == outs["gathered"]


@pytest.mark.parametrize("keys_a_step,rows_a_tile", [(16, 8), (8, 8)],
                         ids=["2-pages-2-tiles", "1-page-2-tiles"])
def test_fused_matches_gathered_in_several_steps_and_tiles(
        monkeypatch, keys_a_step, rows_a_tile):
    """The same scenario with the kernel's tiling forced small, so that
    the server's own calls walk each stream in several loop steps and tile
    each prefill bucket over its rows (at the toy's default tiling one step
    and one tile hold everything): token-identical still."""
    from neural_networks_parallel_training_with_mpi_tpu.ops import (
        pallas_kernels,
    )

    monkeypatch.setattr(pallas_kernels, "_UNTIMED_KEYS", keys_a_step)
    monkeypatch.setattr(pallas_kernels, "_UNTIMED_ROWS", rows_a_tile)
    assert pallas_kernels.paged_tiles(8, 32, 16, 1, 8) \
        == (keys_a_step // 8, rows_a_tile)
    model = _model()
    params = model.init(prng.init_key(0))
    outs = {}
    for impl in ("gathered", "fused"):
        srv = PagedDecodeServer(model, params, slots=4, num_blocks=40,
                                block_size=8, attn_impl=impl)
        outs[impl] = _staggered_scenario(srv)
    assert outs["fused"] == outs["gathered"]


def test_fused_evict_readmit_reproduces_tokens():
    """Mid-stream eviction discards device state; the fused path's greedy
    re-run after re-admission must land the same tokens the gathered
    path produces end to end (same geometry as the parity pin, so the
    core lane pays steps, not a fresh compile)."""
    model = _model()
    params = model.init(prng.init_key(0))
    srv = PagedDecodeServer(model, params, slots=4, num_blocks=40,
                            block_size=8, attn_impl="fused")
    rid = srv.try_admit([4, 5, 6], 10)
    while not srv.prefill_step(rid, 16):
        pass
    srv.step(); srv.step(); srv.step()            # mid-flight
    prompt, max_new = srv.evict(rid)
    srv.allocator.assert_drained()
    rid2 = srv.try_admit(prompt, max_new)
    got = _drain(srv, rid2)
    ref_srv = PagedDecodeServer(model, params, slots=4, num_blocks=40,
                                block_size=8, attn_impl="gathered")
    ref = _drain(ref_srv, ref_srv.try_admit([4, 5, 6], 10))
    assert got == ref
    srv.allocator.assert_drained()


def test_block_table_churn_never_recompiles():
    """The recompile invariant (acceptance criterion): tables and lengths
    are traced scalar-prefetch operands, so admission, on-demand block
    growth, eviction and re-admission all re-run ONE compiled decode
    step; prefill compiles per pow2 bucket width, never per table.
    (The jitted programs are lru-shared across equal-geometry servers,
    so the pin is "no growth after churn", measured on this process's
    shared cache.)"""
    model = _model()
    params = model.init(prng.init_key(0))
    srv = PagedDecodeServer(model, params, slots=4, num_blocks=40,
                            block_size=8, attn_impl="fused")
    a = srv.try_admit([1] * 12, 12)               # bucket 16 + growth
    while not srv.prefill_step(a, 16):
        pass
    for _ in range(4):
        srv.step()
    # the jitted programs are lru-shared across servers, and OTHER
    # geometries (slots / pool size) legitimately add cache entries in a
    # shared pytest process — the invariant is zero growth from here on
    n_step = srv._step_fn._cache_size()
    n_prefill = srv._prefill_fn._cache_size()
    # churn: a second stream (new table rows, new lengths), growth across
    # a block boundary, an eviction (table zeroed to the sink), and a
    # re-admission — same bucket widths, so NOTHING may recompile
    b = srv.try_admit([9] * 11, 8)
    while not srv.prefill_step(b, 16):
        pass
    srv.step()
    srv.evict(b)
    c = srv.try_admit([3] * 9, 6)
    while not srv.prefill_step(c, 16):
        pass
    while not (srv.done(a) and srv.done(c)):
        srv.step()
    srv.result(a), srv.result(c)
    srv.allocator.assert_drained()
    assert srv._step_fn._cache_size() == n_step
    assert srv._prefill_fn._cache_size() == n_prefill


def test_donation_audit_fused_decode_program():
    """The donation audit extended to the fused serving decode program:
    it donates the KV pools, the token slab and the position vector
    (donate_argnums=(1, 2, 4)) — every donated leaf must alias in/out
    (an unaliased pool leaf would copy the whole block pool per decoded
    token)."""
    import jax

    from neural_networks_parallel_training_with_mpi_tpu.utils.profiling import (
        donation_report,
    )

    model = _model()
    params = model.init(prng.init_key(0))
    srv = PagedDecodeServer(model, params, slots=4, num_blocks=24,
                            block_size=8, attn_impl="fused")
    masked = np.where(srv.active[:, None], srv.tables, 0)
    comp = srv._step_fn.lower(
        srv.params, srv.pools, srv.stats, srv.tokens, jnp.asarray(masked),
        srv.pos,
        jnp.asarray(srv.active), srv.key).compile()
    rep = donation_report(comp)
    donated = len(jax.tree_util.tree_leaves(srv.pools)) + 2  # + tokens, pos
    assert rep["n_aliased"] == donated, rep
    assert rep["unaliased_donors"] == 0, rep


# the per-head serving programs' lowered text (StableHLO, no locations; the
# kernel interpreted, so its whole body is in the text) at ``starcoder2-3b``'s
# shapes and 2 layers, as commit 50eb37a (PR 31) lowered them: ISSUE 32 gave
# the kernel a second mode beside this one and asked that this one not move
# (on the chip the programs' compile-cache keys then stay).  ``prefill`` was
# re-pinned by PR 38 (the chunk's head on its last true column alone, under
# ``last``), which left ``decode`` byte for byte.  A PR that means to change
# the per-head path re-pins what it moves.
_PER_HEAD_TEXT_SHA256 = {
    "decode": "0faf555c209d3d7b064b0fbfbb2e7604dddb5cd969cc9ed8fe388380f5de2546",
    "prefill": "98e5d7fefbdfb23e9aa8634718d1ee1e04ea6a0c9b2219903f3ca7fba4eb3cb4",
}


def test_per_head_programs_lower_to_the_parents_text():
    import hashlib

    import jax

    from neural_networks_parallel_training_with_mpi_tpu.serve import paged_kv

    model = Transformer(TransformerConfig(
        vocab_size=49152, max_seq_len=16384, n_layers=2, d_model=3072,
        n_heads=24, n_kv_heads=2, d_ff=12288, pos_encoding="rope",
        rope_theta=999999.44, param_dtype=jnp.bfloat16,
        compute_dtype=jnp.bfloat16))
    spec = jax.ShapeDtypeStruct
    abstract = lambda tree: jax.tree_util.tree_map(            # noqa: E731
        lambda x: spec(x.shape, x.dtype), tree)
    params = abstract(jax.eval_shape(lambda: model.init(prng.init_key(0))))
    pools = abstract(jax.eval_shape(lambda: paged_kv.init_paged_kv(
        model, 2305, 16, folded=True)))
    prefill, step, _, _ = paged_kv._paged_programs(
        model, 16, 256, 0.0, 0, 1.0, False, "fused")
    s, mb = 16, 256
    text = {
        "decode": step.lower(
            params, pools, {}, spec((s, mb * 16), jnp.int32),
            spec((s, mb), jnp.int32), spec((s,), jnp.int32),
            spec((s,), jnp.bool_), spec((2,), jnp.uint32)).as_text(),
        "prefill": prefill.lower(
            params, pools, {}, spec((1, mb), jnp.int32),
            spec((1,), jnp.int32), spec((1, 512), jnp.int32),
            spec((), jnp.int32), spec((), jnp.bool_)).as_text()}
    got = {k: hashlib.sha256(v.encode()).hexdigest() for k, v in text.items()}
    assert got == _PER_HEAD_TEXT_SHA256


# ---------------------------------------------------------------------------
# model-variant parity (full lane: each variant is a fresh compile)
# ---------------------------------------------------------------------------

def _ab_tokens(model, params, prompt, n, prefill_width=16, **srv_kw):
    outs = []
    for impl in ("gathered", "fused"):
        srv = PagedDecodeServer(model, params, slots=2, num_blocks=20,
                                block_size=8, attn_impl=impl, **srv_kw)
        rid = srv.try_admit(prompt, n)
        outs.append(_drain(srv, rid, prefill_width))
        srv.allocator.assert_drained()
    return outs


@pytest.mark.slow
def test_gqa_fused_exact():
    model = _model(n_kv_heads=2)
    params = model.init(prng.init_key(0))
    g, f = _ab_tokens(model, params, [1, 2, 3], 8)
    assert f == g


@pytest.mark.slow
def test_int8_kv_fused_exact():
    """int8 pools: the kernel dequantizes on load from the same
    per-(position, head) scales the gathered path applies to its
    logits/probs — chunked prefill splitting blocks included."""
    model = _model()
    params = model.init(prng.init_key(0))
    g, f = _ab_tokens(model, params, [1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 8,
                      prefill_width=4, kv_quant=True)
    assert f == g


@pytest.mark.slow
def test_scan_layers_fused_exact():
    model = _model(scan_layers=True)
    params = model.init(prng.init_key(0))
    g, f = _ab_tokens(model, params, [9, 8, 7], 6)
    assert f == g


@pytest.mark.slow
def test_rope_fused_exact():
    """RoPE rotates at absolute positions; the kernel's q_pos/start
    plumbing must agree with the gathered path's rotation windows."""
    model = _model(pos_encoding="rope")
    params = model.init(prng.init_key(0))
    g, f = _ab_tokens(model, params, [1, 2, 3, 4, 5, 6, 7, 8, 9], 8,
                      prefill_width=4)
    assert f == g
