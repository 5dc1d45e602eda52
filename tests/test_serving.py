"""Train/serve split: snapshot store + batched cluster-assignment service.

Contracts under test (DESIGN.md §10):
  * snapshot freeze/round-trip — capacity bucketing, prefix mask, overflow
    propagation, publication through the engine's `publish=` hook;
  * serve == train — service responses bit-identical to engine labels
    (`nearest_center` on the same snapshot's pool), per version;
  * hot-swap — responses tagged with the producing version, versions
    monotone, swapping never retraces a warm (bucket, capacity) cache;
  * bucket policy — ragged request sizes pad to power-of-two buckets and
    padding rows can never alias a real answer (hypothesis layer).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DPMeansTransaction, OCCEngine, nearest_center
from repro.data import dp_stick_breaking_data
from repro.kernels import ops
from repro.kernels.ref import D2_ATOL, D2_RTOL
from repro.serving import (
    ClusterService, ModelSnapshot, Query, ServeConfig, SnapshotStore,
    freeze_snapshot, next_bucket,
)
from repro.serving import cluster_service as cs_mod

LAM = 4.0


def _stream(n=768, seed=0, dim=8):
    x, _, _ = dp_stick_breaking_data(n, seed=seed, dim=dim)
    return jnp.asarray(x)


def _trained_store(x, pb=64, k_max=128, batches=((0, 300), (300, 768))):
    store = SnapshotStore(capacity=64)
    eng = OCCEngine(DPMeansTransaction(LAM, k_max=k_max), pb=pb,
                    publish=store.publish_pass)
    for lo, hi in batches:
        eng.partial_fit(x[lo:hi])
    eng.flush()
    return store, eng


# ------------------------------------------------------------- snapshots

def test_freeze_snapshot_capacity_bucketing_and_prefix():
    x = _stream()
    _, eng = _trained_store(x)
    snap = freeze_snapshot(eng.pool, version=7, n_seen=eng.n_processed)
    k = int(eng.pool.count)
    assert snap.version == 7 and snap.count == k
    assert snap.capacity == next_bucket(k) and snap.capacity >= k
    assert snap.capacity & (snap.capacity - 1) == 0
    # prefix compaction preserves the live centers exactly
    np.testing.assert_array_equal(np.asarray(snap.centers[:k]),
                                  np.asarray(eng.pool.centers[:k]))
    assert np.array_equal(np.asarray(snap.mask), np.arange(snap.capacity) < k)
    # as_pool round-trips into the engine-side primitive
    d2s, ids = nearest_center(snap.as_pool(), x[:50], backend="ref")
    d2e, ide = nearest_center(eng.pool, x[:50], backend="ref")
    assert np.array_equal(np.asarray(ids), np.asarray(ide))
    np.testing.assert_allclose(np.asarray(d2s), np.asarray(d2e),
                               rtol=D2_RTOL, atol=D2_ATOL)


def test_snapshot_overflow_epoch_roundtrip():
    """Publishing through a pool-overflow epoch surfaces overflow on the
    snapshot; the snapshot stays servable (full capacity, valid prefix)."""
    x = _stream()
    store = SnapshotStore()
    eng = OCCEngine(DPMeansTransaction(0.01, k_max=8), pb=64,
                    publish=store.publish_pass)
    eng.partial_fit(x[:256])
    snap = store.latest()
    assert snap.overflow and snap.count == 8 and snap.capacity == 8
    svc = ClusterService(store, backend="ref")
    resp = svc.assign(x[:16])
    assert resp.version == snap.version
    assert (resp.labels >= 0).all() and (resp.labels < 8).all()


def test_engine_publish_hook_stream_metadata():
    """One version per committed pass; carry-only calls publish nothing;
    flush publishes the final short epoch; metadata tracks the stream."""
    x = _stream()
    store = SnapshotStore()
    eng = OCCEngine(DPMeansTransaction(LAM, k_max=128), pb=64,
                    publish=store.publish_pass)
    eng.partial_fit(x[:30])                  # carry only -> no version
    assert len(store) == 0 and eng.n_pending == 30
    eng.partial_fit(x[30:300])               # commits 4 epochs, carries 44
    assert len(store) == 1
    assert store.latest().n_seen == 256 and store.latest().epochs == 4
    eng.partial_fit(x[300:750])              # commits 7 more, carries 46
    assert len(store) == 2 and eng.n_pending == 46
    eng.flush()                              # final short epoch
    assert len(store) == 3
    assert store.latest().n_seen == 750 and store.latest().epochs == 12
    versions = store.versions()
    assert versions == sorted(versions)
    # published pool == streaming pool at each publish point (last one)
    np.testing.assert_array_equal(
        np.asarray(store.latest().centers[:store.latest().count]),
        np.asarray(eng.pool.centers[:int(eng.pool.count)]))


def test_store_ring_eviction_keeps_monotone_versions():
    x = _stream(256)
    store = SnapshotStore(capacity=2)
    eng = OCCEngine(DPMeansTransaction(LAM, k_max=64), pb=32,
                    publish=store.publish_pass)
    for i in range(0, 256, 64):
        eng.partial_fit(x[i:i + 64])
    assert len(store) == 2
    assert store.versions() == [3, 4]        # FIFO eviction, monotone ids
    assert store.get(1) is None and store.get(4) is not None
    assert store.latest().version == 4


# ------------------------------------------------------ serve == train

def test_service_assign_bit_identical_to_engine_labels():
    x = _stream()
    store, eng = _trained_store(x)
    svc = ClusterService(store, backend="ref")
    resp = svc.score(x[:100])
    snap = store.get(resp.version)
    d2e, ide = nearest_center(snap.as_pool(), x[:100], backend="ref")
    assert np.array_equal(resp.labels, np.asarray(ide))
    assert resp.labels.dtype == np.int32
    # scores are the squared distances of the assigned centers
    np.testing.assert_allclose(resp.scores, np.asarray(d2e), atol=1e-5)


def test_service_response_replayable_from_tagged_version():
    """Zero stale reads: the tagged snapshot reproduces the response
    bit-exactly through the service's own jitted step."""
    x = _stream()
    store, eng = _trained_store(x)
    svc = ClusterService(store, backend="ref")
    resp = svc.score(x[:77])
    snap = store.get(resp.version)
    qp = jnp.concatenate(
        [x[:77], jnp.zeros((resp.bucket - 77, x.shape[1]), x.dtype)], 0)
    d2, idx = cs_mod._assign_step(snap.centers, snap.mask,
                                  np.int32(snap.count), qp, np.int32(77),
                                  backend="ref")
    assert np.array_equal(resp.labels, np.asarray(idx[:77]))
    np.testing.assert_array_equal(resp.scores, np.asarray(d2[:77]))


def test_hot_swap_between_microbatches_no_retrace():
    """New versions are picked up between microbatches; a version change
    within the same (bucket, capacity) never recompiles the query step."""
    x = _stream()
    store = SnapshotStore()
    eng = OCCEngine(DPMeansTransaction(LAM, k_max=128), pb=64,
                    publish=store.publish_pass)
    eng.partial_fit(x[:256])
    svc = ClusterService(store, backend="ref")
    r1 = svc.assign(x[:40])
    v1 = r1.version
    eng.partial_fit(x[256:512])              # publishes a newer version
    # republish the same pool shape to pin the capacity bucket, then prove
    # a pure version change is free: same (bucket, capacity) -> no retrace
    svc.assign(x[:40])                       # may retrace if capacity grew
    traces0 = cs_mod._QUERY_TRACES
    store.publish_pool(eng.pool)
    r2 = svc.assign(x[:40])
    assert r2.version > v1
    assert svc.n_swaps >= 2
    assert cs_mod._QUERY_TRACES == traces0   # warm cache across the swap
    # the old version still audits against its own snapshot
    old = store.get(v1)
    _, ide = nearest_center(old.as_pool(), x[:40], backend="ref")
    assert np.array_equal(r1.labels, np.asarray(ide))
    assert svc.n_dispatches == svc.n_microbatches


def test_topk_and_score_coherence():
    x = _stream()
    store, _ = _trained_store(x)
    svc = ClusterService(store, backend="ref")
    k = min(4, store.latest().count)
    rt = svc.topk(x[:25], k=k)
    ra = svc.score(x[:25])
    assert rt.labels.shape == (25, k)
    assert np.array_equal(rt.labels[:, 0], ra.labels)     # top-1 == assign
    np.testing.assert_allclose(rt.scores[:, 0], ra.scores,
                               rtol=D2_RTOL, atol=D2_ATOL)
    assert (np.diff(rt.scores, axis=1) >= 0).all()        # ascending
    # matches a full sort of the reference distance matrix
    snap = store.get(rt.version)
    d2, idx = ops.serve_topk(x[:25], snap.centers, k, mask=snap.mask,
                             count=jnp.asarray(snap.count, jnp.int32))
    assert np.array_equal(rt.labels, np.asarray(idx))


def test_service_with_mesh_replicated_snapshot():
    """The mesh serving path (replicated snapshot + data-sharded queries)
    compiles and stays bit-identical to the meshless service.  One-device
    mesh here; the multi-device placement is the same GSPMD program (see
    shardings.serve_snapshot_sharding / serve_query_sharding)."""
    x = _stream()
    store, _ = _trained_store(x)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    svc_m = ClusterService(store, backend="ref", mesh=mesh)
    svc_0 = ClusterService(store, backend="ref")
    rm, r0 = svc_m.score(x[:48]), svc_0.score(x[:48])
    assert rm.version == r0.version
    assert np.array_equal(rm.labels, r0.labels)
    np.testing.assert_array_equal(rm.scores, r0.scores)
    tm = svc_m.topk(x[:16], k=2)
    assert np.array_equal(tm.labels, svc_0.topk(x[:16], k=2).labels)


def test_service_no_version_raises():
    svc = ClusterService(SnapshotStore(), backend="ref")
    with pytest.raises(RuntimeError):
        svc.assign(jnp.zeros((4, 8)))


def test_giant_request_splits_with_single_version():
    x = _stream()
    store, _ = _trained_store(x)
    svc = ClusterService(store, backend="ref", max_bucket=128)
    before = svc.n_microbatches
    resp = svc.score(x[:300])                # 3 microbatches of <=128
    assert resp.labels.shape == (300,)
    assert svc.n_microbatches - before == 3
    snap = store.get(resp.version)
    _, ide = nearest_center(snap.as_pool(), x[:300], backend="ref")
    assert np.array_equal(resp.labels, np.asarray(ide))


# --------------------------------------------------------- bucket policy

def test_bucket_rounding_and_padding_mask():
    x = _stream()
    store, _ = _trained_store(x)
    svc = ClusterService(store, backend="ref", min_bucket=8, max_bucket=256)
    for n, want in [(1, 8), (8, 8), (9, 16), (100, 128), (256, 256)]:
        resp = svc.assign(x[:n])
        assert resp.bucket == want, (n, resp.bucket)
        assert resp.labels.shape == (n,)
        assert (resp.labels >= 0).all()      # padding never leaks out


def test_bucketed_emulation_parity_on_serving_shapes():
    """The kernel, emulated by Pallas interpret mode, parity-checks a
    production serving bucket (4096 queries x 512-capacity snapshot)
    against the jnp oracle."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(4096, 32)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(512, 32)).astype(np.float32))
    count = 301
    m = jnp.asarray(np.arange(512) < count)
    d2p, ip = ops.serve_assign(x, c, m, count=jnp.asarray(count, jnp.int32),
                               n_valid=jnp.asarray(4000, jnp.int32),
                               backend="pallas")
    d2r, ir = ops.serve_assign(x, c, m, count=jnp.asarray(count, jnp.int32),
                               n_valid=jnp.asarray(4000, jnp.int32),
                               backend="ref")
    assert np.array_equal(np.asarray(ip), np.asarray(ir))
    np.testing.assert_allclose(np.asarray(d2p[:4000]), np.asarray(d2r[:4000]),
                               atol=1e-3)
    assert (np.asarray(ip[4000:]) == -1).all()
    assert np.isinf(np.asarray(d2p[4000:])).all()


# -------------------------------------------------------- hypothesis layer

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(sizes=st.lists(st.integers(min_value=1, max_value=200),
                          min_size=1, max_size=6))
    def test_hypothesis_ragged_requests_parity(sizes):
        """Any sequence of ragged request sizes: every response's labels
        match the engine labels on its tagged version, buckets are powers
        of two >= the request, and version tags are monotone."""
        x = _stream(512, seed=7)
        store, _ = _trained_store(x, batches=((0, 512),))
        svc = ClusterService(store, backend="ref", min_bucket=8,
                             max_bucket=256)
        rng = np.random.default_rng(11)
        last_v = -1
        for n in sizes:
            lo = int(rng.integers(0, 512 - n)) if n < 512 else 0
            resp = svc.score(x[lo:lo + n])
            assert resp.bucket >= min(n, 256)
            assert resp.bucket & (resp.bucket - 1) == 0
            assert resp.version >= last_v
            last_v = resp.version
            snap = store.get(resp.version)
            _, ide = nearest_center(snap.as_pool(), x[lo:lo + n],
                                    backend="ref")
            assert np.array_equal(resp.labels, np.asarray(ide))
else:  # pragma: no cover - exercised only without hypothesis
    def test_hypothesis_layer_skipped():
        pytest.skip("hypothesis not installed; deterministic layer still ran")


# ------------------------------- hierarchical layout + multi-probe (§16)

def _hier_store(x, pb=64, k_max=128, batches=((0, 300), (300, 768)),
                lam=1.0, **hier_kw):
    # lam=1.0 grows the pool to ~128 centers (16 coarse cells) — enough
    # cells that a small probe width actually prunes; LAM=4 yields 4
    # centers / 2 cells, where probes >= n_cells degenerates to flat.
    store = SnapshotStore(capacity=64, hier=True, **hier_kw)
    eng = OCCEngine(DPMeansTransaction(lam, k_max=k_max), pb=pb,
                    publish=store.publish_pass)
    for lo, hi in batches:
        eng.partial_fit(x[lo:hi])
    eng.flush()
    return store, eng


def test_hier_build_invariants_and_flat_bit_identity():
    """The hierarchical layout is a pure access-path permutation: fine
    shards partition the active prefix [0, count) exactly once, every
    shard row is a bit-copy of its flat row, and the snapshot's FLAT
    buffers are bit-identical to a hier=False publish of the same pool."""
    x = _stream()
    store_h, eng = _hier_store(x)
    store_f = SnapshotStore(capacity=64)
    store_f.publish_pool(eng.pool)
    sh, sf = store_h.latest(), store_f.latest()
    np.testing.assert_array_equal(np.asarray(sh.centers),
                                  np.asarray(sf.centers))
    np.testing.assert_array_equal(np.asarray(sh.mask), np.asarray(sf.mask))
    h = sh.hier
    assert h is not None and sf.hier is None
    count = int(sh.count)
    assert h.n_cells & (h.n_cells - 1) == 0 and h.n_cells <= count
    assert h.shard_cap & (h.shard_cap - 1) == 0
    ids, msk = np.asarray(h.fine_ids), np.asarray(h.fine_mask)
    np.testing.assert_array_equal(np.sort(ids[msk]), np.arange(count))
    assert (ids[~msk] == -1).all()
    fine, flat = np.asarray(h.fine), np.asarray(sh.centers)
    r, c = np.nonzero(msk)
    np.testing.assert_array_equal(fine[r, c], flat[ids[r, c]])
    assert (fine[~msk] == 0).all()
    # coarse rows are bit-copies of active-prefix centers
    assert np.asarray(h.coarse_mask).all()
    coarse = np.asarray(h.coarse)
    assert all((coarse[i] == flat[:count]).all(1).any()
               for i in range(h.n_cells))


def test_hier_delta_store_materializes_same_layout():
    """Delta-mode stores build the hier at first materialize; the layout
    must equal the eager store's bit for bit (same builder, same prefix)."""
    x = _stream()
    store_h, eng = _hier_store(x)
    store_d = SnapshotStore(capacity=64, hier=True, delta=True)
    store_d.publish_pool(eng.pool)
    he = store_h.latest().hier
    hd = store_d.latest().materialize().hier if hasattr(
        store_d.latest(), "materialize") else store_d.latest().hier
    assert hd is not None
    np.testing.assert_array_equal(np.asarray(hd.fine_ids),
                                  np.asarray(he.fine_ids))
    np.testing.assert_array_equal(np.asarray(hd.fine), np.asarray(he.fine))
    np.testing.assert_array_equal(np.asarray(hd.coarse),
                                  np.asarray(he.coarse))


def test_service_multiprobe_p_all_bit_identical_to_flat():
    """The exactness contract: probes >= n_cells routes the FLAT step, so
    responses are bit-identical to a probes=None service — and a hier
    store serves plain flat queries unchanged."""
    x = _stream()
    store, _ = _hier_store(x)
    n_cells = store.latest().hier.n_cells
    flat = ClusterService(store, backend="ref", audit_log=True)
    pall = ClusterService(store, backend="ref", probes=n_cells,
                          audit_log=True)
    q = np.asarray(x[100:137])
    r_f, r_a = flat.topk(q, k=7), pall.topk(q, k=7)
    np.testing.assert_array_equal(r_f.labels, r_a.labels)
    np.testing.assert_array_equal(r_f.scores, r_a.scores)
    assert pall.audit[-1].probes == 0        # flat dispatch, by construction
    assert pall.metrics()["n_topk_multiprobe"] == 0


def test_service_multiprobe_counters_recall_and_audit_record():
    x = _stream()
    store, _ = _hier_store(x)
    h = store.latest().hier
    svc = ClusterService(store, backend="ref", probes=2,
                         recall_audit_every=2, audit_log=True)
    q = np.asarray(x[:40])
    for _ in range(4):
        resp = svc.topk(q, k=5)
    met = svc.metrics()
    assert met["n_topk_multiprobe"] == 4
    assert met["topk_probes"] == 2
    assert 0 < met["topk_shards_probed"] <= 4 * h.n_cells
    assert met["topk_tiles_skipped"] == 4 * h.n_cells - met["topk_shards_probed"]
    assert met["topk_recall_audits"] == 2    # every 2nd of 4 dispatches
    assert 0.0 < met["topk_recall"] <= 1.0
    assert svc.audit[-1].probes == 2
    # responses stay well-formed: valid ids in [0, count), ascending d2
    labels, scores = resp.labels, resp.scores
    assert ((labels >= -1) & (labels < int(store.latest().count))).all()
    valid = labels >= 0
    assert np.isfinite(scores[valid]).all()


def test_service_multiprobe_backend_parity_and_no_retrace():
    """ref and pallas services agree through the full multi-probe path
    (indices exactly, distances to f32 tolerance), and a version hot-swap
    does not retrace the warm multi-probe step."""
    x = _stream()
    store, eng = _hier_store(x)
    q = np.asarray(x[200:232])
    svc_r = ClusterService(store, backend="ref", probes=2)
    svc_p = ClusterService(store, backend="pallas", probes=2)
    r_r, r_p = svc_r.topk(q, k=6), svc_p.topk(q, k=6)
    np.testing.assert_array_equal(r_r.labels, r_p.labels)
    np.testing.assert_allclose(r_r.scores, r_p.scores, atol=1e-5)
    traces0 = cs_mod._QUERY_TRACES
    store.publish_pool(eng.pool)             # new version, same buckets
    r2 = svc_r.topk(q, k=6)
    assert cs_mod._QUERY_TRACES == traces0   # warm cache across versions
    assert r2.version > r_r.version


def test_service_probes_requires_hier_snapshot():
    x = _stream()
    store, _ = _trained_store(x)             # hier=False store
    svc = ClusterService(store, backend="ref", probes=2)
    with pytest.raises(RuntimeError, match="hier"):
        svc.topk(np.asarray(x[:8]), k=3)


# --------------------------------------------------- §17 typed surface

def test_shims_bit_identical_to_submit_query_solo():
    """`assign`/`score`/`topk` are pure shims over `submit(Query(...))`:
    every response field bit-identical on the solo path."""
    x = _stream()
    store, _ = _trained_store(x)
    svc = ClusterService(store, backend="ref")
    q = np.asarray(x[:33])
    pairs = [
        (svc.score(q), svc.submit(Query(q))),
        (svc.assign(q), svc.submit(Query(q, want_scores=False))),
        (svc.topk(q, k=5), svc.submit(Query(q, kind="topk", k=5))),
    ]
    for shim, typed in pairs:
        assert shim.version == typed.version
        assert shim.bucket == typed.bucket
        assert shim.group == typed.group == -1
        assert shim.degraded == typed.degraded is False
        np.testing.assert_array_equal(shim.labels, typed.labels)
        if shim.scores is None:
            assert typed.scores is None
        else:
            np.testing.assert_array_equal(shim.scores, typed.scores)


def test_shims_bit_identical_to_submit_query_coalesced():
    """Same identity through the admission queue: the shims land in the
    same (kind, k, lane) groups the typed form does."""
    x = _stream()
    store, _ = _trained_store(x)
    svc = ClusterService(store, backend="ref", coalesce=True,
                         coalesce_bucket=64, coalesce_delay_ms=5.0)
    try:
        q = np.asarray(x[:17])
        shim, typed = svc.score(q), svc.submit(Query(q))
        assert shim.version == typed.version
        assert shim.group >= 0 and typed.group >= 0
        np.testing.assert_array_equal(shim.labels, typed.labels)
        np.testing.assert_array_equal(shim.scores, typed.scores)
        tk = svc.submit(Query(q, kind="topk", k=4))
        np.testing.assert_array_equal(svc.topk(q, k=4).labels, tk.labels)
    finally:
        svc.close()


def test_serve_config_object_and_keyword_forms_agree():
    """`ClusterService(store, ServeConfig(...))` and the historical
    keyword form resolve to the same construction; keyword overrides
    patch a passed config."""
    x = _stream()
    store, _ = _trained_store(x)
    cfg = ServeConfig(backend="ref", min_bucket=16, coalesce_bucket=128)
    svc_a = ClusterService(store, cfg, max_bucket=256)
    svc_b = ClusterService(store, backend="ref", min_bucket=16,
                           coalesce_bucket=128, max_bucket=256)
    assert svc_a.config == svc_b.config
    assert (svc_a.backend, svc_a.min_bucket, svc_a.max_bucket) == \
        ("ref", 16, 256)
    assert svc_a.config.coalesce_bucket == 128
    q = np.asarray(x[:9])
    ra, rb = svc_a.score(q), svc_b.score(q)
    assert ra.version == rb.version and ra.bucket == rb.bucket == 16
    np.testing.assert_array_equal(ra.labels, rb.labels)
