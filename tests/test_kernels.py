"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret=True."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.serving import ServeConfig


@pytest.mark.parametrize("n,k,d", [(17, 5, 3), (64, 32, 16), (100, 37, 16),
                                   (256, 128, 64), (33, 130, 8)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_dpmeans_assign_sweep(rng, n, k, d, dtype):
    x = jnp.asarray(rng.normal(size=(n, d)).astype(dtype))
    c = jnp.asarray(rng.normal(size=(k, d)).astype(dtype))
    m = jnp.asarray(rng.uniform(size=k) > 0.25)
    d2p, ip = ops.pairwise_argmin(x, c, m, backend="pallas",
                                  block_n=32, block_k=16)
    d2r, ir = ref.pairwise_argmin_ref(x, c, m)
    np.testing.assert_allclose(np.asarray(d2p), np.asarray(d2r),
                               atol=5e-3 if dtype == np.float16 else 1e-4)
    np.testing.assert_array_equal(np.asarray(ip), np.asarray(ir))


def test_dpmeans_assign_empty_mask(rng):
    x = jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(4, 4)).astype(np.float32))
    m = jnp.zeros((4,), bool)
    d2, idx = ops.pairwise_argmin(x, c, m, backend="pallas", block_n=8, block_k=4)
    assert np.all(np.isinf(np.asarray(d2)))
    assert np.all(np.asarray(idx) == -1)    # kernel contract: -1 when empty


_SMALL_BLOCKS = {"block_n": 16, "block_k": 8}


@pytest.mark.parametrize("n,k,d,count,blocks", [
    pytest.param(*case, None, _SMALL_BLOCKS, id="-".join(map(str, case)))
    for case in [
        (5, 3, 2),        # n and k both below the minimum tile
        (9, 5, 4),        # K < 8: bk clamps up, k-padding fills the tile
        (7, 130, 8),      # ragged K across many tiles, ragged n
        (130, 7, 16),     # ragged N across tiles, K < 8
        (31, 33, 5),      # both non-multiples of the block sizes
    ]
] + [
    pytest.param(*case, _SMALL_BLOCKS, id="-".join(map(str, case)))
    for case in [(17, 5, 3, None), (33, 130, 8, 37), (20, 37, 6, 0),
                 (20, 37, 6, 8), (7, 130, 8, 100)]
] + [
    # 128-lane running state: two lane slices per 256-wide tile, three
    # tiles, the third beyond the count
    pytest.param(40, 600, 12, 300, {"block_n": 16, "block_k": 256},
                 id="lane-slices"),
    # the default tiles: one row block, 512-wide center tiles
    pytest.param(24, 1000, 8, None, {}, id="default-tiles"),
    # one 1024-wide tile computed in four 256-wide column groups
    pytest.param(512, 1024, 8, 700, {}, id="column-groups"),
])
def test_dpmeans_assign_interpret_ragged_parity(rng, n, k, d, count, blocks):
    """Interpret-mode Pallas vs the reference on ragged N/K shapes
    (non-multiples of block sizes, K < 8) — exactly the awkward pool sizes
    the OCC engine produces — with and without an active-prefix count, and
    on the schedule's own shapes: lane slices, default tiles, column
    groups."""
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
    m = (jnp.asarray(np.arange(k) < count) if count is not None
         else jnp.asarray(rng.uniform(size=k) > 0.3))
    cnt = None if count is None else jnp.asarray(count, jnp.int32)
    d2p, ip = ops.assign(x, c, m, count=cnt, backend="pallas", **blocks)
    d2r, ir = ops.assign(x, c, m, count=cnt, backend="ref")
    np.testing.assert_allclose(np.asarray(d2p), np.asarray(d2r), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(ip), np.asarray(ir))


@pytest.mark.parametrize("count", [0, 3, 7, 8, 37])
def test_dpmeans_assign_count_prefix_parity(rng, count):
    """The count-rounded active prefix: tiles beyond `count` are skipped on
    the Pallas path; results must equal the reference with the prefix mask.
    Covers count == 0 (empty pool) and count == K (all tiles active)."""
    n, k, d = 20, 37, 6
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
    # pool invariant: valid slots are a prefix of the buffer
    m = jnp.asarray(np.arange(k) < count)
    cnt = jnp.asarray(count, jnp.int32)
    d2p, ip = ops.assign(x, c, m, count=cnt, backend="pallas",
                         block_n=16, block_k=8)
    d2r, ir = ops.assign(x, c, m, count=cnt, backend="ref")
    np.testing.assert_allclose(np.asarray(d2p), np.asarray(d2r), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(ip), np.asarray(ir))
    if count == 0:
        assert np.all(np.asarray(ip) == -1)


@pytest.mark.parametrize("n,d,k", [
    (256, 96, 65536),      # deep96.train: pb=256 at DEEP width
    (1024, 512, 65536),    # laion512.train: pb=1024 at CLIP width
    (256, 512, 65536),     # laion512.train.4chip: 256 rows a chip
    (8, 96, 32768),        # the smallest serving bucket
    (4096, 96, 32768),     # the largest serving bucket
    (1500, 96, 1000),      # rows split evenly, K not a power of two
    (100, 8, 37),          # K below one lane width
    (1024, 4096, 65536),   # too wide for 1024 rows: the block shrinks
])
def test_assign_tiles(n, d, k):
    """The default tiles: one row block up to 1024 rows, the widest
    power-of-two multiple of 128 centers (at most K) within the VMEM
    budget, and live steps = ceil(count / bk) per row block."""
    from repro.kernels.dpmeans_assign import (
        VMEM_BUDGET, assign_tile_steps, assign_tiles)
    bn, bk = assign_tiles(n, d, k)
    blocks = -(-n // bn)
    if n <= 1024 and d <= 512:
        assert blocks == 1 and bn == max(n, 8)
    assert bn % 8 == 0 or bn == n
    assert (bn * d + bk * d + bn * bk) * 4 * 2 <= VMEM_BUDGET
    if k < 128:
        assert bk == max(8, k)
    else:
        assert bk % 128 == 0 and bk <= k
        assert (bk // 128) & (bk // 128 - 1) == 0
        wider = 2 * bk
        assert (wider > k
                or (bn * d + wider * d + bn * wider) * 4 * 2 > VMEM_BUDGET)
    k_tiles = -(-k // bk)
    for count in (0, 1, k // 3, k // 2 + 7, k):
        assert assign_tile_steps(count, n, d, k) == (
            blocks * k_tiles, blocks * -(-count // bk))


def test_assign_tiles_at_the_cells():
    """Tiles, grid steps and live steps of one propose epoch at the three
    benchmark cells' shapes and pool sizes (PERF.md records them)."""
    from repro.kernels.dpmeans_assign import assign_tile_steps, assign_tiles
    assert assign_tiles(256, 96, 65536) == (256, 4096)
    assert assign_tile_steps(33986, 256, 96, 65536) == (16, 9)
    assert assign_tiles(1024, 512, 65536) == (1024, 512)
    assert assign_tile_steps(52552, 1024, 512, 65536) == (128, 103)
    assert assign_tiles(256, 512, 65536) == (256, 1024)
    assert assign_tile_steps(52552, 256, 512, 65536) == (64, 52)


def test_assign_ref_matches_legacy_nearest_center_semantics(rng):
    """ops.assign(ref) == masked sq_dists min/argmin with -1 on empty — the
    exact contract core.occ.nearest_center is built on."""
    from repro.core.objective import sq_dists
    x = jnp.asarray(rng.normal(size=(12, 5)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(9, 5)).astype(np.float32))
    m = jnp.asarray(np.arange(9) < 4)
    d2, idx = ops.assign(x, c, m, count=jnp.asarray(4, jnp.int32),
                         backend="ref")
    d2_ref = jnp.where(m[None, :], sq_dists(x, c), jnp.inf)
    np.testing.assert_array_equal(np.asarray(d2),
                                  np.asarray(jnp.min(d2_ref, -1)))
    np.testing.assert_array_equal(np.asarray(idx),
                                  np.asarray(jnp.argmin(d2_ref, -1)))


@pytest.mark.parametrize("b,h,hkv,s,dh", [(1, 4, 4, 128, 32), (2, 8, 2, 128, 32),
                                          (2, 4, 1, 256, 64)])
def test_flash_attention_sweep(rng, b, h, hkv, s, dh):
    q = jnp.asarray(rng.normal(size=(b, h, s, dh)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, hkv, s, dh)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, hkv, s, dh)).astype(np.float32))
    op = ops.flash_attention(q, k, v, backend="pallas", block_q=64, block_k=64)
    orf = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(op), np.asarray(orf), atol=2e-3)


def test_flash_attention_noncausal(rng):
    q = jnp.asarray(rng.normal(size=(1, 2, 128, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 2, 128, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 2, 128, 16)).astype(np.float32))
    op = ops.flash_attention(q, k, v, causal=False, backend="pallas",
                             block_q=64, block_k=64)
    orf = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(op), np.asarray(orf), atol=2e-3)


@pytest.mark.parametrize("shape", [(7, 33), (64, 256), (3, 5, 128)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_rmsnorm_sweep(rng, shape, dtype):
    x = jnp.asarray(rng.normal(size=shape).astype(dtype))
    w = jnp.asarray(rng.normal(size=shape[-1]).astype(dtype))
    got = ops.rmsnorm(x, w, backend="pallas", block_rows=16)
    want = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=1e-2 if dtype == np.float16 else 1e-5)


@pytest.mark.parametrize("shape", [(5, 17), (128, 512), (2, 3, 64)])
def test_swiglu_sweep(rng, shape):
    g = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    u = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    got = ops.swiglu(g, u, backend="pallas", block_rows=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.swiglu_ref(g, u)),
                               atol=1e-6)


def test_backend_resolution():
    assert not ops.on_tpu()
    for bad in ("nope", "emulate"):
        with pytest.raises(ValueError):
            ops._resolve(bad)


@pytest.mark.parametrize("construct", [
    pytest.param(lambda: ServeConfig(backend="emulate"), id="serve-config"),
    pytest.param(lambda: ops.assign(jnp.zeros((8, 4)), jnp.zeros((8, 4)),
                                    backend="emulate"), id="ops-assign"),
])
def test_backend_outside_the_three_rejected(construct):
    """`auto`, `pallas` and `ref` are the only backends: the serving
    config refuses any other at construction, the kernel entry points at
    the call."""
    with pytest.raises(ValueError, match="backend"):
        construct()


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_assign_duplicate_centers_lowest_index_wins(rng, backend):
    """Exact duplicate centers tie exactly; the lowest index wins on every
    backend: a pair in two lanes of one tile (3, 70), a pair in one lane
    of two lane slices (9, 137), and a pair across tiles (5, 300)."""
    k, d = 512, 8
    c = rng.normal(size=(k, d)).astype(np.float32)
    pairs = [(3, 70), (9, 137), (5, 300)]
    for lo, hi in pairs:
        c[hi] = c[lo]
    x = np.concatenate([c[[lo for lo, _ in pairs]],
                        c[[lo for lo, _ in pairs]]
                        + 1e-3 * rng.normal(size=(3, d)).astype(np.float32)])
    kw = {} if backend == "ref" else {"block_n": 8, "block_k": 256}
    d2, idx = ops.assign(jnp.asarray(x), jnp.asarray(c),
                         count=jnp.asarray(400, jnp.int32), backend=backend,
                         **kw)
    lows = [lo for lo, _ in pairs]
    np.testing.assert_array_equal(np.asarray(idx), lows + lows)


def test_assign_production_shape_parity(rng):
    """A serving-bucket-sized shape with the default tiles (two 1024-row
    blocks against one 1024-wide center tile) in interpret mode, against
    the oracle."""
    x = jnp.asarray(rng.normal(size=(2048, 48)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(1024, 48)).astype(np.float32))
    count = 517
    m = jnp.asarray(np.arange(1024) < count)
    cnt = jnp.asarray(count, jnp.int32)
    d2p, ip = ops.assign(x, c, m, count=cnt, backend="pallas")
    d2r, ir = ops.assign(x, c, m, count=cnt, backend="ref")
    np.testing.assert_allclose(np.asarray(d2p), np.asarray(d2r), atol=1e-3)
    np.testing.assert_array_equal(np.asarray(ip), np.asarray(ir))


# ------------------------------------------------- the screened assign path

def _unit(a):
    return (a / np.linalg.norm(a, axis=-1, keepdims=True)).astype(np.float32)


def _screen_case(rng, case, n, d, k):
    """(x, centers, count, certified): rows and a pool of unit vectors with
    the case planted.  `certified` says whether every row must pass the
    screen's band test (None: either)."""
    x = _unit(rng.normal(size=(n, d)))
    c = _unit(rng.normal(size=(k, d)))
    count, certified = k - 37, None

    def plant(row, col, d2):
        # a center at squared distance d2 from the row, off a random axis
        v = _unit(rng.normal(size=d))
        v -= (v @ x[row]) * x[row]
        c[col] = x[row] + np.sqrt(d2) * _unit(v)

    if case == "random":
        certified = None
    elif case in ("near-tie-lanes", "near-tie-lane"):
        # the nearer of two centers 2e-5 apart (inside the band) at the
        # higher index: in other lanes, or in one lane 128 columns apart
        gap = 1 if case == "near-tie-lanes" else 128
        for r in range(0, n, 3):
            lo = (7 * r) % (count - 2 * 128)
            plant(r, lo, 0.5 + 2e-5)
            plant(r, lo + gap, 0.5)
        certified = case == "near-tie-lanes"
    elif case in ("exact-tie-lanes", "exact-tie-lane"):
        gap = 1 if case == "exact-tie-lanes" else 128
        for r in range(0, n, 5):
            lo = (11 * r) % (count - 2 * 128)
            plant(r, lo, 0.4)
            c[lo + gap] = c[lo]
        certified = case == "exact-tie-lanes"
    elif case == "empty":
        count, certified = 0, True
    elif case == "norms":
        x *= 10.0 ** rng.uniform(-2, 2, size=(n, 1)).astype(np.float32)
        c *= 10.0 ** rng.uniform(-2, 2, size=(k, 1)).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(c), count, certified


_SCREEN_SHAPES = [pytest.param(128, 96, 1024, id="d96"),
                  pytest.param(128, 512, 1024, id="d512")]


@pytest.mark.parametrize("n,d,k", _SCREEN_SHAPES)
@pytest.mark.parametrize("case", ["random", "near-tie-lanes",
                                  "near-tie-lane", "exact-tie-lanes",
                                  "exact-tie-lane", "empty", "norms"])
def test_screened_assign_is_the_highest_answer(rng, case, n, d, k):
    """The screen, the re-score and the fallback together give
    `ref.assign_ref`'s answer: the same ids, d2 within the distance
    contract.  Near-ties across lanes stay certified and the re-score
    orders them; near-ties and exact ties inside one lane (columns i and
    i + 128) fail the band test, and the call falls back to the exact
    kernel."""
    from repro.kernels.dpmeans_assign import (SCREEN_MIN_ROWS,
                                              dpmeans_assign_screen,
                                              screened_assign)
    assert n >= SCREEN_MIN_ROWS
    x, c, count, certified = _screen_case(rng, case, n, d, k)
    m = jnp.arange(k) < count
    cnt = jnp.asarray(count, jnp.int32)
    blocks = {"block_k": 256, "interpret": True}
    _, cand, cert = dpmeans_assign_screen(x, c, m, count=cnt, **blocks)
    if certified is not None:
        assert bool(jnp.all(cert)) == certified
    d2, idx = screened_assign(x, c, m, count=cnt, **blocks)
    d2r, ir = ref.assign_ref(x, c, m)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ir))
    np.testing.assert_allclose(np.asarray(d2), np.asarray(d2r),
                               rtol=ref.D2_RTOL, atol=ref.D2_ATOL)
    # a certified row's HIGHEST nearest center is one of its candidates
    hit = np.any(np.asarray(cand) == np.asarray(ir)[:, None], axis=1)
    assert np.all(hit | ~np.asarray(cert) | (np.asarray(ir) < 0))
    if case == "empty":
        assert np.all(np.asarray(idx) == -1) and np.all(np.isinf(d2))


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("kind", ["random", "near-duplicates", "norms",
                                  "offset"])
def test_screened_minimum_within_the_bound(kind, seed):
    """|screened - HIGHEST| <= E for every pair, so each row's screened
    minimum lies within E of the HIGHEST minimum: E = kappa_D ||x|| c_max
    + rho_D (||x||^2 + c_max^2) (`screen_bound`).  Adversarial rows:
    centers within 1e-3 of the rows (cancellation in x^2 + c^2 - 2 x.c),
    norms over four decades, and rows far off the origin."""
    from repro.kernels.dpmeans_assign import (dpmeans_assign_screen,
                                              screen_bound)
    rng = np.random.default_rng(seed)
    n, d, k = 64, 96 if seed % 2 else 512, 512
    x = _unit(rng.normal(size=(n, d)))
    c = _unit(rng.normal(size=(k, d)))
    if kind == "near-duplicates":
        c[:n] = x + 1e-3 * rng.normal(size=(n, d)).astype(np.float32)
    elif kind == "norms":
        x *= 10.0 ** rng.uniform(-2, 2, size=(n, 1)).astype(np.float32)
        c *= 10.0 ** rng.uniform(-2, 2, size=(k, 1)).astype(np.float32)
    elif kind == "offset":
        shift = 30.0 * _unit(rng.normal(size=(1, d)))
        x, c = x + shift, c + shift
    m = jnp.ones((k,), bool)
    d2s, _, _ = dpmeans_assign_screen(jnp.asarray(x), jnp.asarray(c), m,
                                      block_k=256, interpret=True)
    d2r, _ = ref.assign_ref(jnp.asarray(x), jnp.asarray(c), m)
    kappa, rho = screen_bound(d)
    x2 = (x.astype(np.float64) ** 2).sum(1)
    c2max = (c.astype(np.float64) ** 2).sum(1).max()
    bound = kappa * np.sqrt(x2 * c2max) + rho * (x2 + c2max)
    err = np.abs(np.asarray(d2s, np.float64) - np.asarray(d2r, np.float64))
    assert np.all(err <= bound), (err / bound).max()


def test_screen_bound_values():
    """kappa_D and rho_D at the cells' widths: for unit rows E is 1.4e-4
    at D = 96 and 3.5e-4 at D = 512."""
    from repro.kernels.dpmeans_assign import screen_bound
    k96, r96 = screen_bound(96)
    k512, r512 = screen_bound(512)
    assert k96 + 2 * r96 == pytest.approx(1.42e-4, rel=0.01)
    assert k512 + 2 * r512 == pytest.approx(3.45e-4, rel=0.01)
    assert 3 * 2.0 ** -16 * 2 < k96 < k512


@pytest.mark.parametrize("n,d,k,screen", [
    (256, 96, 65536, True),       # deep96.train, deep96-ofl.train
    (1024, 512, 65536, True),     # laion512.train
    (256, 512, 65536, True),      # laion512.train.4chip, a chip's rows
    (64, 96, 32768, False),       # a serving bucket below R
    (256, 96, 4096, False),       # one center tile
])
def test_takes_screen(n, d, k, screen):
    from repro.kernels.dpmeans_assign import takes_screen
    assert takes_screen(n, d, k) == screen


def test_assign_routes_by_shape(rng):
    """`ops.assign` on the Pallas path takes the screen at >= R rows and
    more than one center tile, and the exact kernel alone otherwise, or
    where the caller sets the tiles."""
    import re

    import jax
    from repro.kernels.dpmeans_assign import SCREEN_MIN_ROWS
    k, d = 8192, 96

    def kernels(n, **blocks):
        x = jnp.zeros((n, d))
        c = jnp.zeros((k, d))
        names = set(re.findall(r"name=(\w+)", str(jax.make_jaxpr(
            lambda x, c: ops.assign(x, c, backend="pallas", **blocks))(
                x, c))))
        return "dpmeans_assign_screen" in names, "dpmeans_assign" in names

    assert kernels(SCREEN_MIN_ROWS) == (True, True)
    assert kernels(SCREEN_MIN_ROWS - 8) == (False, True)
    assert kernels(SCREEN_MIN_ROWS, block_k=512) == (False, True)


# --------------------------------------------------- serving-plane entries

def test_serve_assign_query_prefix_masking(rng):
    """Bucket padding rows come back (inf, -1) on every backend; real rows
    equal plain `assign`."""
    x = jnp.asarray(rng.normal(size=(32, 6)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(16, 6)).astype(np.float32))
    m = jnp.asarray(np.arange(16) < 9)
    cnt = jnp.asarray(9, jnp.int32)
    nv = jnp.asarray(20, jnp.int32)
    for backend in ("ref", "pallas"):
        kw = {} if backend == "ref" else {"block_n": 16, "block_k": 8}
        d2, idx = ops.serve_assign(x, c, m, count=cnt, n_valid=nv,
                                   backend=backend, **kw)
        d2a, ia = ops.assign(x, c, m, count=cnt, backend=backend, **kw)
        np.testing.assert_array_equal(np.asarray(idx[:20]),
                                      np.asarray(ia[:20]))
        np.testing.assert_array_equal(np.asarray(d2[:20]),
                                      np.asarray(d2a[:20]))
        assert (np.asarray(idx[20:]) == -1).all()
        assert np.isinf(np.asarray(d2[20:])).all()


def test_serve_topk_matches_full_sort(rng):
    from repro.core.objective import sq_dists
    x = jnp.asarray(rng.normal(size=(15, 7)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(20, 7)).astype(np.float32))
    count = 13
    m = jnp.asarray(np.arange(20) < count)
    d2k, idxk = ops.serve_topk(x, c, 5, mask=m,
                               count=jnp.asarray(count, jnp.int32),
                               n_valid=jnp.asarray(12, jnp.int32))
    full = np.where(np.arange(20)[None, :] < count,
                    np.asarray(sq_dists(x, c)), np.inf)
    order = np.argsort(full, axis=1, kind="stable")[:, :5]
    np.testing.assert_array_equal(np.asarray(idxk[:12]), order[:12])
    assert (np.diff(np.asarray(d2k[:12]), axis=1) >= 0).all()
    assert (np.asarray(idxk[12:]) == -1).all()
    # top-1 column == serve_assign verdict (same algebra, same ties)
    _, ia = ops.serve_assign(x, c, m, count=jnp.asarray(count, jnp.int32),
                             backend="ref")
    np.testing.assert_array_equal(np.asarray(idxk[:12, 0]),
                                  np.asarray(ia[:12]))


def test_serve_topk_active_prefix_immune_to_garbage_slots(rng):
    """Slots beyond the active prefix may hold arbitrary stale payloads —
    including NaN/inf — after pool reuse or snapshot capacity padding.
    `serve_topk` scores only the active prefix (masked rows are zeroed
    before the matmul), so garbage slots can neither surface in the top-k
    nor perturb the scores of valid slots, and asking for k > count yields
    clean (inf, -1) tails rather than garbage indices."""
    x = jnp.asarray(rng.normal(size=(9, 6)).astype(np.float32))
    c_clean = jnp.asarray(rng.normal(size=(16, 6)).astype(np.float32))
    count = 5
    poisoned = c_clean.at[count:].set(jnp.nan).at[count + 1].set(jnp.inf)
    cnt = jnp.asarray(count, jnp.int32)
    k = 8                                     # > count: forces padded tail
    d2_ref, idx_ref = ops.serve_topk(x, c_clean, k, count=cnt)
    d2_poi, idx_poi = ops.serve_topk(x, poisoned, k, count=cnt)
    np.testing.assert_array_equal(np.asarray(idx_ref), np.asarray(idx_poi))
    np.testing.assert_array_equal(np.asarray(d2_ref), np.asarray(d2_poi))
    assert (np.asarray(idx_poi) < count).all()            # never a padded slot
    assert (np.asarray(idx_poi[:, count:]) == -1).all()   # clean k>count tail
    assert np.isinf(np.asarray(d2_poi[:, count:])).all()
    assert np.isfinite(np.asarray(d2_poi[:, :count])).all()


# ------------------------------------------- streaming top-k (DESIGN.md §16)
#
# Parity tiers, per the §16 precision note: for f32 inputs the streamed
# merge is candidate-multiset-invariant, and at MXU-aligned shapes (D a
# lane multiple, K a block multiple) XLA CPU reproduces the tile matmuls
# bitwise against the flat one — so aligned shapes assert BITWISE equality
# of (d2, idx) between ref and interpret.  At deliberately awkward shapes
# (D=19, K=300) the last-ulp of the d2 reduction may differ between
# tilings, so ragged sweeps assert idx exactly + d2 to 1e-5.

from repro.kernels.topk_stream import center_tile, topk_tile_loads
from repro.serving.snapshot import build_hier


@pytest.mark.parametrize("n,kc,d,count,k", [
    (17, 20, 5, 13, 4),      # ragged everything
    (37, 300, 19, 211, 7),   # many tiles, awkward D
    (9, 20, 6, 5, 8),        # k > count: padded tail
    (20, 37, 6, 0, 3),       # empty pool
    (33, 130, 8, 130, 5),    # count == K, all tiles active
])
def test_topk_stream_ragged_parity(rng, n, kc, d, count, k):
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(kc, d)).astype(np.float32))
    m = jnp.asarray(np.arange(kc) < count)
    cnt = jnp.asarray(count, jnp.int32)
    d2r, ir = ops.serve_topk(x, c, k, mask=m, count=cnt, backend="ref")
    d2p, ip = ops.serve_topk(x, c, k, mask=m, count=cnt, backend="pallas",
                             block_n=16, block_k=8)
    np.testing.assert_array_equal(np.asarray(ip), np.asarray(ir))
    np.testing.assert_allclose(np.asarray(d2p), np.asarray(d2r), atol=1e-5)


def test_topk_stream_bitwise_at_aligned_shapes(rng):
    """MXU-aligned serving shapes: kernel and oracle bit-identical in
    BOTH distances and indices, ragged active prefix included."""
    x = jnp.asarray(rng.normal(size=(64, 64)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(512, 64)).astype(np.float32))
    count = 387
    m = jnp.asarray(np.arange(512) < count)
    cnt = jnp.asarray(count, jnp.int32)
    d2r, ir = ops.serve_topk(x, c, 8, mask=m, count=cnt, backend="ref")
    d2p, ip = ops.serve_topk(x, c, 8, mask=m, count=cnt, backend="pallas",
                             block_n=32, block_k=128)
    np.testing.assert_array_equal(np.asarray(d2p), np.asarray(d2r))
    np.testing.assert_array_equal(np.asarray(ip), np.asarray(ir))


def test_topk_top1_column_equals_serve_assign(rng):
    """topk[:, :1] == serve_assign on each backend — same algebra, same
    lower-index tie order (the contract layered services rely on)."""
    x = jnp.asarray(rng.normal(size=(31, 16)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(64, 16)).astype(np.float32))
    cnt = jnp.asarray(41, jnp.int32)
    m = jnp.asarray(np.arange(64) < 41)
    for backend in ("ref", "pallas"):
        kw = {} if backend == "ref" else {"block_n": 16, "block_k": 8}
        d2k, ik = ops.serve_topk(x, c, 3, mask=m, count=cnt,
                                 backend=backend, **kw)
        d2a, ia = ops.serve_assign(x, c, m, count=cnt, backend=backend,
                                   **kw)
        np.testing.assert_array_equal(np.asarray(ik[:, 0]), np.asarray(ia))


def test_topk_static_count_slicing_bitwise(rng):
    """A HOST-int count lets the CPU reference slice to the pow2 active
    prefix pre-matmul (named as "ref" or resolved from "auto"); the result must be bitwise what the traced-count full-
    width dispatch produces (a prefix slice changes no surviving lane)."""
    x = jnp.asarray(rng.normal(size=(16, 16)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(1024, 16)).astype(np.float32))
    count = 53                                # pow2 pad -> 64 of 1024
    m = jnp.asarray(np.arange(1024) < count)
    for backend in ("ref", "auto"):
        d2s, is_ = ops.serve_topk(x, c, 6, mask=m, count=count,
                                  backend=backend)
        d2t, it = ops.serve_topk(x, c, 6, mask=m,
                                 count=jnp.asarray(count, jnp.int32),
                                 backend=backend)
        np.testing.assert_array_equal(np.asarray(d2s), np.asarray(d2t))
        np.testing.assert_array_equal(np.asarray(is_), np.asarray(it))


@pytest.mark.parametrize("count", [0, 1, 5, 64, 130, 300, 512])
def test_topk_tile_loads_accounting(rng, count):
    """The host-side walk of the kernel's own index map (`center_tile`):
    tiles beyond the active prefix issue ZERO loads (the dpmeans_assign
    assertion style, applied to the top-k schedule), the map traced as
    the kernel traces it agrees, and the kernel at that count answers as
    the oracle does."""
    kc, bk = 512, 128
    walk = topk_tile_loads(count, kc, block_k=bk)
    assert walk == max(1, -(-count // bk))    # active tiles only
    assert walk <= kc // bk                   # never the full-K sweep
    tiles = np.arange(kc // bk)
    np.testing.assert_array_equal(
        np.asarray(center_tile(jnp.asarray(tiles),
                               jnp.asarray(count, jnp.int32), bk)),
        center_tile(tiles, count, bk, np))
    x = jnp.asarray(rng.normal(size=(8, 16)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(kc, 16)).astype(np.float32))
    m = jnp.asarray(np.arange(kc) < count)
    cnt = jnp.asarray(count, jnp.int32)
    d2p, ip = ops.serve_topk(x, c, 4, mask=m, count=cnt, backend="pallas",
                             block_k=bk)
    d2r, ir = ops.serve_topk(x, c, 4, mask=m, count=cnt, backend="ref")
    np.testing.assert_array_equal(np.asarray(ip), np.asarray(ir))
    np.testing.assert_allclose(np.asarray(d2p), np.asarray(d2r), atol=1e-5)


def test_topk_k_exceeds_capacity_padded_columns(rng):
    """k > buffer capacity: overflow columns are (inf, -1) on every
    backend, real columns untouched."""
    x = jnp.asarray(rng.normal(size=(7, 5)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(12, 5)).astype(np.float32))
    cnt = jnp.asarray(12, jnp.int32)
    for backend in ("ref", "pallas"):
        kw = {} if backend == "ref" else {"block_n": 8, "block_k": 8}
        d2, idx = ops.serve_topk(x, c, 20, count=cnt, backend=backend, **kw)
        assert d2.shape == (7, 20)
        assert (np.asarray(idx[:, 12:]) == -1).all()
        assert np.isinf(np.asarray(d2[:, 12:])).all()
        assert (np.asarray(idx[:, :12]) >= 0).all()


def test_topk_duplicate_distance_tiebreak_determinism(rng):
    """Duplicated center rows force exact distance ties; every backend
    must break them identically — ascending index within each tie run
    (lax.top_k's order, pinned by the lexicographic (d2, id) merge)."""
    base = rng.normal(size=(8, 6)).astype(np.float32)
    c = jnp.asarray(np.repeat(base, 3, axis=0))        # rows 3i,3i+1,3i+2 equal
    x = jnp.asarray(rng.normal(size=(11, 6)).astype(np.float32))
    cnt = jnp.asarray(24, jnp.int32)
    outs = {}
    for backend in ("ref", "pallas"):
        kw = {} if backend == "ref" else {"block_n": 8, "block_k": 8}
        d2, idx = ops.serve_topk(x, c, 6, count=cnt, backend=backend, **kw)
        outs[backend] = (np.asarray(d2), np.asarray(idx))
    np.testing.assert_array_equal(outs["pallas"][1], outs["ref"][1])
    np.testing.assert_allclose(outs["pallas"][0], outs["ref"][0],
                               rtol=ref.D2_RTOL, atol=ref.D2_ATOL)
    d2, idx = outs["ref"]
    for r in range(11):
        for j in range(1, 6):
            if d2[r, j] == d2[r, j - 1]:               # exact tie
                assert idx[r, j] > idx[r, j - 1]       # ascending ids
        # duplicates: each triple's members surface lowest-index first
        assert idx[r, 0] % 3 == 0                      # nearest triple's row 3i


def test_topk_multiprobe_full_union_bitwise_flat(rng):
    """p = all at the ops level: union covering every cell + all-true
    membership equals flat serve_topk on every backend under the distance
    contract (ids exact, distances to ref.D2_RTOL/D2_ATOL) — garbage in
    padded shard slots included."""
    kc, d, count = 512, 64, 437
    cn = rng.normal(size=(kc, d)).astype(np.float32)
    cn[count:] = np.nan
    m = jnp.asarray(np.arange(kc) < count)
    h = build_hier(jnp.asarray(np.nan_to_num(cn)), m, count)
    x = jnp.asarray(rng.normal(size=(32, d)).astype(np.float32))
    cells = jnp.arange(h.n_cells, dtype=jnp.int32)
    member = jnp.ones((32, h.n_cells), bool)
    d2f, if_ = ops.serve_topk(x, jnp.asarray(np.nan_to_num(cn)), 9, mask=m,
                              count=jnp.asarray(count, jnp.int32),
                              backend="ref")
    for backend in ("ref", "pallas"):
        d2m, im = ops.serve_topk_multiprobe(
            x, h.fine, h.fine_ids, h.fine_mask, cells, member, 9,
            u_count=jnp.asarray(h.n_cells, jnp.int32), backend=backend)
        np.testing.assert_allclose(np.asarray(d2m), np.asarray(d2f),
                                   rtol=ref.D2_RTOL, atol=ref.D2_ATOL)
        np.testing.assert_array_equal(np.asarray(im), np.asarray(if_))


_REF_D2 = np.array([[0.5, 1.0, 1.0 + 2e-6, 3.0, 4.0]], np.float32)
_REF_ID = np.array([[7, 3, 9, 1, 2]], np.int32)


@pytest.mark.parametrize("d2,idx,bad", [
    ([[0.5, 1.0, 1.0, 3.0]], [[7, 3, 9, 1]], 0),           # equal
    ([[0.5 + 1e-6, 1.0, 1.0, 3.0]], [[7, 3, 9, 1]], 0),    # last-ulp distance
    ([[0.5, 1.0, 1.0, 3.0]], [[7, 9, 3, 1]], 0),           # near-tie swap
    ([[0.5, 1.0, 1.0, 3.0]], [[3, 7, 9, 1]], 2),           # swap without tie
    ([[0.5, 1.0, 1.0, 3.1]], [[7, 3, 9, 1]], 1),           # distance miss
    ([[0.5, 1.0, 1.0, 3.0]], [[7, 3, 9, 5]], 1),           # id not in ref row
])
def test_topk_disagreements_distance_contract(d2, idx, bad):
    """ids exact except where reference distances tie within tolerance;
    distances to D2_RTOL/D2_ATOL."""
    assert ref.topk_disagreements(np.array(d2, np.float32),
                                  np.array(idx, np.int32),
                                  _REF_D2, _REF_ID) == bad


def test_topk_multiprobe_partial_union_matches_candidate_oracle(rng):
    """Partial probes: backends agree on indices exactly (distances to f32
    tolerance — the gathered widths here are deliberately unaligned, §16
    precision note) AND match a brute-force numpy top-k over exactly the
    probed candidate set."""
    kc, d, count = 256, 16, 201
    cn = rng.normal(size=(kc, d)).astype(np.float32)
    m = jnp.asarray(np.arange(kc) < count)
    h = build_hier(jnp.asarray(cn), m, count)
    b, k = 9, 5
    x = rng.normal(size=(b, d)).astype(np.float32)
    probed = np.sort(rng.choice(h.n_cells, size=3, replace=False))
    cells = np.full((h.n_cells,), -1, np.int32)
    cells[:3] = probed
    member = np.zeros((b, h.n_cells), bool)
    member[:, :3] = rng.uniform(size=(b, 3)) > 0.3
    outs = {}
    for backend in ("ref", "pallas"):
        outs[backend] = ops.serve_topk_multiprobe(
            x, h.fine, h.fine_ids, h.fine_mask, jnp.asarray(cells),
            jnp.asarray(member), k, u_count=jnp.asarray(3, jnp.int32),
            backend=backend)
    np.testing.assert_array_equal(np.asarray(outs["pallas"][1]),
                                  np.asarray(outs["ref"][1]))
    np.testing.assert_allclose(np.asarray(outs["pallas"][0]),
                               np.asarray(outs["ref"][0]), atol=1e-5)
    # brute force over the candidate multiset
    ids = np.asarray(h.fine_ids)
    msk = np.asarray(h.fine_mask)
    d2o, io_ = np.asarray(outs["ref"][0]), np.asarray(outs["ref"][1])
    for q in range(b):
        cand = [int(i) for u in range(3) if member[q, u]
                for i in ids[probed[u]][msk[probed[u]]]]
        dd = np.sort([float(np.sum((x[q] - cn[i]) ** 2)) for i in cand])
        got = io_[q][io_[q] >= 0]
        assert len(got) == min(k, len(cand))
        np.testing.assert_allclose(np.sort(d2o[q][np.isfinite(d2o[q])]),
                                   dd[:len(got)], atol=1e-4)
        assert set(got) <= set(cand)


# ------------------------------------ hypothesis layer (streaming top-k)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(data=st.data())
    def test_hypothesis_topk_stream_parity(data):
        """Any (n, K, count, k, duplicate run): ref and interpret agree on
        indices exactly, distances to f32 tolerance, tails are (inf, -1),
        and rows are lexicographically (d2, idx) ascending."""
        n = data.draw(st.integers(1, 40), label="n")
        kc = data.draw(st.integers(1, 200), label="K")
        count = data.draw(st.integers(0, kc), label="count")
        k = data.draw(st.integers(1, 12), label="k")
        dup = data.draw(st.booleans(), label="dup")
        rng = np.random.default_rng(data.draw(st.integers(0, 2**31),
                                              label="seed"))
        c = rng.normal(size=(kc, 8)).astype(np.float32)
        if dup and kc >= 2:
            c[1::2] = c[0::2][: c[1::2].shape[0]]      # force exact ties
        x = jnp.asarray(rng.normal(size=(n, 8)).astype(np.float32))
        m = jnp.asarray(np.arange(kc) < count)
        cnt = jnp.asarray(count, jnp.int32)
        d2r, ir = ops.serve_topk(x, jnp.asarray(c), k, mask=m, count=cnt,
                                 backend="ref")
        d2p, ip = ops.serve_topk(x, jnp.asarray(c), k, mask=m, count=cnt,
                                 backend="pallas", block_n=16, block_k=8)
        np.testing.assert_array_equal(np.asarray(ip), np.asarray(ir))
        np.testing.assert_allclose(np.asarray(d2p), np.asarray(d2r),
                                   atol=1e-5)
        d2, idx = np.asarray(d2r), np.asarray(ir)
        valid = idx >= 0
        assert (valid.sum(1) == min(k, count)).all()
        assert np.isinf(d2[~valid]).all()
        for r in range(n):                     # lexicographic ascending
            row_d, row_i = d2[r][valid[r]], idx[r][valid[r]]
            assert (np.diff(row_d) >= 0).all()
            same = np.diff(row_d) == 0
            assert (np.diff(row_i)[same] > 0).all()
else:  # pragma: no cover - exercised only without hypothesis
    def test_hypothesis_topk_layer_skipped():
        pytest.skip("hypothesis not installed; deterministic layer still ran")
