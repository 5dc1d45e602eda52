#!/usr/bin/env python3
"""Smoke run of the OCC train-and-serve path on a TPU.

    python chip_smoke.py [--seed 0]      # one chip: train, check, serve
    python chip_smoke.py --chips 4       # mesh-sharded OCC pass vs one chip

One process, run from the root of a checkout (it puts `src` on the path
itself).  It trains DP-means with the OCC engine on 2^20 unit-norm points
at D=96, the width of big-ann-benchmarks' DEEP set, checks the pass against
the float32 reference validator, publishes the model into a hierarchical
snapshot store and serves assign, flat top-k and multi-probe top-k requests
from it through `ClusterService.submit`.  Every answer is checked against
the reference under the distance contract of `repro.kernels.ref` (ids
exact, distances to a stated tolerance).  Any failed check exits non-zero;
so does a run that finds no TPU.  On success the last line of stdout is one
JSON object naming the device.  The times it prints are informational, not
metrics.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

D = 96                  # DEEP (big-ann-benchmarks) vector width
N_LOG2 = 20             # training points
K_MAX = 32768           # center capacity: FAISS's IVF range tops out at 16√N
PB = 256                # points per OCC epoch (the paper's P·b)
N_BATCHES = 4           # partial_fit calls the stream arrives in
N_COMP = 8192           # mixture components
NOISE = 0.5             # total noise variance per point, before normalizing
# Same-component points end up at squared distance ~0.67 from each other,
# different components at >~1.3, so λ² = 1 yields one center per component.
LAM = 1.0
REF_N_LOG2 = 16         # prefix checked against the reference validator
K_TOP = 10
ASSIGN_REQ, ASSIGN_ROWS = 128, 256
TOPK_REQ, TOPK_ROWS = 128, 64
MP_REQ, MP_ALL_REQ = 64, 16
P_SMALL = 8             # coarse cells probed per query


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"ok: {what}")


def mixture(seed: int, n: int, n_comp: int = N_COMP, d: int = D,
            noise: float = NOISE):
    """n unit-norm points drawn on the device from a Gaussian mixture with
    n_comp random unit-norm means and isotropic noise of total variance
    `noise`."""
    import jax
    import jax.numpy as jnp

    def unit(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)

    @jax.jit
    def draw(key):
        km, kz, ke = jax.random.split(key, 3)
        means = unit(jax.random.normal(km, (n_comp, d), jnp.float32))
        z = jax.random.randint(kz, (n,), 0, n_comp)
        eps = jax.random.normal(ke, (n, d), jnp.float32)
        return unit(means[z] + jnp.sqrt(noise / d) * eps)

    return draw(jax.random.key(seed))


class CompileClock:
    """Seconds spent in XLA backend compilation (cache hits excluded)."""

    def __init__(self):
        import jax
        self.seconds = 0.0

        def on_event(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        jax.monitoring.register_event_duration_secs_listener(on_event)


def kernel_in(jitted, *args, **kwargs) -> bool:
    """Is a Pallas TPU kernel in the compiled program of `jitted`?"""
    return "tpu_custom_call" in jitted.lower(*args, **kwargs).compile().as_text()


def train(x, *, lam=LAM, k_max=K_MAX, pb=PB, n_batches=N_BATCHES,
          mesh=None, store=None):
    """Stream x through OCCEngine.partial_fit in n_batches calls + flush.
    Returns (engine, per-point assignments, seconds per call)."""
    import jax
    import jax.numpy as jnp
    from repro.core import DPMeansTransaction, OCCEngine

    eng = OCCEngine(DPMeansTransaction(lam, k_max=k_max), pb=pb,
                    validate_cap="adaptive", mesh=mesh,
                    publish=None if store is None else store.publish_pass)
    n = x.shape[0]
    step = -(-n // n_batches)
    assigns, secs = [], []
    for lo in range(0, n, step):
        t0 = time.perf_counter()
        res = eng.partial_fit(x[lo:lo + step])
        jax.block_until_ready(res.assign)
        secs.append(time.perf_counter() - t0)
        assigns.append(res.assign)
    res = eng.flush()
    if res is not None:
        assigns.append(res.assign)
    return eng, jnp.concatenate(assigns), secs


def engine_pass_has_kernel(eng, x_batch, mesh=None) -> bool:
    """Compile the cold full-width pass the stream's first call ran and
    look for the propose kernel in it."""
    from repro.core.engine import _engine_pass_jit
    pool = eng.txn.init_pool(x_batch[:eng.pb])
    return kernel_in(_engine_pass_jit, eng.txn, pool, x_batch, (), pb=eng.pb,
                     cap_warm=None, cap_rest=None, n_warm=0, n_bootstrap=0,
                     mesh=mesh, data_axis=eng.data_axis)


def reference_check(x, *, lam=LAM, k_max=K_MAX, pb=PB) -> None:
    """One engine pass against `core/_reference.reference_pass` — the
    legacy validator that recomputes every distance per step — run at
    highest matmul precision.  Serial equivalence (paper Thm 3.1) holds
    only if both saw the same distances: same centers, same assignments,
    same proposals."""
    import jax
    import numpy as np
    from repro.core import DPMeansTransaction, OCCEngine
    from repro.core._reference import reference_pass

    txn = DPMeansTransaction(lam, k_max=k_max)
    res = OCCEngine(txn, pb=pb, validate_cap="adaptive").run(x)
    with jax.default_matmul_precision("highest"):
        pool_r, assign_r, send_r, _ = reference_pass(
            txn, txn.init_pool(x[:pb]), x, pb=pb)
    k = int(res.pool.count)
    check(k == int(pool_r.count),
          f"reference check: engine K={k} == reference K={int(pool_r.count)}")
    check(np.array_equal(np.asarray(res.pool.centers[:k]),
                         np.asarray(pool_r.centers[:k])),
          "reference check: centers bit-identical")
    check(np.array_equal(np.asarray(res.assign), np.asarray(assign_r)),
          f"reference check: all {x.shape[0]} assignments identical")
    check(np.array_equal(np.asarray(res.send), np.asarray(send_r)),
          "reference check: proposals identical")


def serve(store, queries, topk_queries) -> None:
    """Assign, flat top-k and multi-probe top-k requests through
    ClusterService.submit, each checked under the distance contract."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ref
    from repro.serving import ClusterService, Query, ServeConfig
    from repro.serving import cluster_service as cs

    snap = store.latest()
    h = snap.hier
    count = np.int32(snap.count)
    print(f"serving version {snap.version}: K={snap.count} "
          f"capacity={snap.capacity} cells={h.n_cells} shard_cap={h.shard_cap}")

    @jax.jit
    def ref_topk(q):
        with jax.default_matmul_precision("highest"):
            return ref.topk_ref(q, snap.centers, K_TOP + 1, snap.mask)

    def reference(q):
        parts = [ref_topk(q[i:i + 4096]) for i in range(0, q.shape[0], 4096)]
        return (np.concatenate([np.asarray(p[0]) for p in parts]),
                np.concatenate([np.asarray(p[1]) for p in parts]))

    # -- assign at bucket 256
    svc = ClusterService(store, ServeConfig())
    t0 = time.perf_counter()
    resp = [svc.submit(Query(queries[i * ASSIGN_ROWS:(i + 1) * ASSIGN_ROWS]))
            for i in range(ASSIGN_REQ)]
    dt = time.perf_counter() - t0
    check(all(r.bucket == ASSIGN_ROWS and r.version == snap.version
              for r in resp), f"{ASSIGN_REQ} assign requests at bucket 256")
    lab = np.concatenate([r.labels for r in resp])
    d2 = np.concatenate([r.scores for r in resp])
    d2r, ir = reference(queries[:ASSIGN_REQ * ASSIGN_ROWS])
    bad = ref.topk_disagreements(d2[:, None], lab[:, None], d2r, ir)
    check(bad == 0, f"assign vs ref.topk_ref top-1: {bad} disagreements "
          f"in {lab.size} rows")
    print(f"assign: {ASSIGN_REQ} requests in {dt:.3f} s "
          "(informational, not a metric)")
    xp = jnp.zeros((ASSIGN_ROWS, D), jnp.float32)
    check(kernel_in(cs._assign_step, snap.centers, snap.mask, count, xp,
                    np.int32(ASSIGN_ROWS), backend="auto"),
          "Pallas kernel in the compiled assign step")

    # -- flat top-k, k=10 at bucket 64
    t0 = time.perf_counter()
    resp = [svc.submit(Query(topk_queries[i * TOPK_ROWS:(i + 1) * TOPK_ROWS],
                             kind="topk", k=K_TOP))
            for i in range(TOPK_REQ)]
    dt = time.perf_counter() - t0
    check(all(r.bucket == TOPK_ROWS for r in resp),
          f"{TOPK_REQ} flat top-{K_TOP} requests at bucket 64")
    flat_i = np.concatenate([r.labels for r in resp])
    flat_d = np.concatenate([r.scores for r in resp])
    d2r, ir = reference(topk_queries)
    bad = ref.topk_disagreements(flat_d, flat_i, d2r, ir)
    check(bad == 0, f"flat top-{K_TOP} vs ref.topk_ref: {bad} disagreements "
          f"in {flat_i.size} slots")
    exact_ids = float(np.mean(flat_i == ir[:, :K_TOP]))
    print(f"flat top-k: {TOPK_REQ} requests in {dt:.3f} s "
          f"(informational, not a metric); ids equal to ref: {exact_ids:.6f}")
    xq = jnp.zeros((TOPK_ROWS, D), jnp.float32)
    check(kernel_in(cs._topk_step, snap.centers, snap.mask, count, xq,
                    np.int32(TOPK_ROWS), k=K_TOP, backend="auto"),
          "Pallas kernel in the compiled flat top-k step")

    # -- multi-probe top-k at a small probe count
    hier = (h.coarse, h.coarse_mask, h.fine, h.fine_ids, h.fine_mask)
    svc_p = ClusterService(store, ServeConfig(probes=P_SMALL))
    rows = MP_REQ * TOPK_ROWS
    resp = [svc_p.submit(Query(topk_queries[i * TOPK_ROWS:(i + 1) * TOPK_ROWS],
                               kind="topk", k=K_TOP))
            for i in range(MP_REQ)]
    mp_i = np.concatenate([r.labels for r in resp])
    mp_d = np.concatenate([r.scores for r in resp])
    qn = np.asarray(topk_queries[:rows], np.float64)
    cn = np.asarray(snap.centers, np.float64)
    valid = mp_i >= 0
    exact = np.where(valid, np.sum(
        (qn[:, None, :] - cn[np.maximum(mp_i, 0)]) ** 2, axis=-1), np.inf)
    tol = ref.D2_ATOL + ref.D2_RTOL * np.where(valid, exact, 0)
    check(bool(valid.all()) and bool(np.all(np.abs(mp_d - exact) <= tol))
          and bool(np.all(np.diff(mp_d, axis=1) >= 0))
          and all(len(set(r)) == K_TOP for r in mp_i),
          f"multi-probe p={P_SMALL}: {mp_i.size} slots are distinct ids at "
          "their true distances, ascending")
    recall = np.mean([len(set(a) & set(e)) / K_TOP
                      for a, e in zip(mp_i, flat_i[:rows])])
    recall1 = np.mean(mp_i[:, 0] == flat_i[:rows, 0])
    print(f"multi-probe p={P_SMALL} of {h.n_cells} cells: recall@{K_TOP} "
          f"vs flat = {recall:.4f}, recall@1 = {recall1:.4f}")
    up = min(h.n_cells, P_SMALL * TOPK_ROWS)
    check(kernel_in(cs._mp_topk_step, *hier, xq, np.int32(TOPK_ROWS),
                    k=K_TOP, p=P_SMALL, u_cap=up, backend="auto"),
          "Pallas kernel in the compiled multi-probe step")

    # -- probes = all: the service serves it as the flat step by design;
    # the multi-probe kernel itself is run over the full union as well.
    svc_a = ClusterService(store, ServeConfig(probes=h.n_cells))
    resp = [svc_a.submit(Query(topk_queries[i * TOPK_ROWS:(i + 1) * TOPK_ROWS],
                               kind="topk", k=K_TOP))
            for i in range(MP_ALL_REQ)]
    all_i = np.concatenate([r.labels for r in resp])
    rows = MP_ALL_REQ * TOPK_ROWS
    check(np.array_equal(all_i, flat_i[:rows]),
          f"service probes=all ({MP_ALL_REQ} requests) == flat top-k")
    mk_d, mk_i, probed = [], [], []
    for i in range(0, rows, TOPK_ROWS):
        d_, i_, n_probed = cs._mp_topk_step(
            *hier, topk_queries[i:i + TOPK_ROWS], np.int32(TOPK_ROWS),
            k=K_TOP, p=h.n_cells, u_cap=h.n_cells, backend="auto")
        mk_d.append(np.asarray(d_))
        mk_i.append(np.asarray(i_))
        probed.append(int(n_probed))
    mk_d, mk_i = np.concatenate(mk_d), np.concatenate(mk_i)
    check(all(p == h.n_cells for p in probed),
          f"multi-probe kernel at p=all streamed all {h.n_cells} shards")
    bad = ref.topk_disagreements(mk_d, mk_i, flat_d[:rows], flat_i[:rows])
    check(bad == 0, f"multi-probe kernel at p=all vs flat: {bad} "
          f"disagreements in {mk_i.size} slots")
    check(kernel_in(cs._mp_topk_step, *hier, xq, np.int32(TOPK_ROWS),
                    k=K_TOP, p=h.n_cells, u_cap=h.n_cells, backend="auto"),
          "Pallas kernel in the compiled p=all multi-probe step")


def one_chip(args, clock) -> None:
    import jax
    import numpy as np
    from repro.kernels import ops
    from repro.serving import SnapshotStore

    check(ops._resolve("auto") == (True, False),
          "default backend resolves to the compiled Pallas kernels")
    n = 1 << N_LOG2
    n_q = ASSIGN_REQ * ASSIGN_ROWS + TOPK_REQ * TOPK_ROWS
    t0 = time.perf_counter()
    data = mixture(args.seed, n + n_q)
    x, queries = data[:n], data[n:n + ASSIGN_REQ * ASSIGN_ROWS]
    topk_queries = data[n + ASSIGN_REQ * ASSIGN_ROWS:]
    jax.block_until_ready(data)
    print(f"data: N={n} D={D} unit-norm, {N_COMP}-component mixture, "
          f"lambda={LAM}, seed={args.seed} ({time.perf_counter() - t0:.2f} s)")

    store = SnapshotStore(hier=True)
    c0 = clock.seconds
    eng, assign, secs = train(x, store=store)
    pool = eng.pool
    k = int(pool.count)
    print(f"train: K={k} from N={n} in {len(secs)} partial_fit calls; "
          f"pass seconds {[round(s, 3) for s in secs]}; compile "
          f"{clock.seconds - c0:.1f} s; caps {eng.cap_history} "
          "(times informational, not metrics)")
    lo = 4 * math.isqrt(n)          # FAISS's IVF nlist range: 4√N..16√N
    check(lo <= k <= K_MAX and not bool(pool.overflow),
          f"K={k} within [{lo}, {K_MAX}] without overflow")
    a = np.asarray(assign)
    check(a.shape == (n,) and a.min() >= 0 and a.max() < k,
          "every point assigned to a live center")
    d2a = np.asarray(jax.jit(
        lambda x, c, z: jax.numpy.sum((x - c[z]) ** 2, axis=-1))(
            x, pool.centers, assign))
    check(bool(np.all(d2a <= LAM ** 2 * (1 + 1e-5))),
          "every point within lambda of its center (DP-means invariant)")
    check(store.latest().count == k, "published snapshot holds the pool")
    check(engine_pass_has_kernel(eng, x[:n // N_BATCHES]),
          "Pallas propose kernel in the compiled OCC pass")

    rn = 1 << REF_N_LOG2
    t0 = time.perf_counter()
    reference_check(x[:rn])
    print(f"reference check on the {rn}-point prefix took "
          f"{time.perf_counter() - t0:.1f} s (informational)")

    serve(store, queries, topk_queries)


def four_chips(args, clock) -> None:
    """The paper's distributed pass (Fig. 4): each epoch's points sharded
    over a 4-chip data axis, against the same pass on one chip."""
    import jax
    import numpy as np

    devs = jax.devices()
    check(len(devs) == 4, f"4 chips present (found {len(devs)})")
    mesh = jax.make_mesh((4,), ("data",), devices=devs,
                         axis_types=(jax.sharding.AxisType.Auto,))
    n = 1 << N_LOG2
    x = mixture(args.seed, n)
    jax.block_until_ready(x)
    print(f"data: N={n} D={D}, lambda={LAM}, seed={args.seed}")
    c0 = clock.seconds
    eng_m, a_m, s_m = train(x, mesh=mesh)
    print(f"sharded over 4 chips: K={int(eng_m.pool.count)}; pass seconds "
          f"{[round(s, 3) for s in s_m]}; compile {clock.seconds - c0:.1f} s "
          "(informational, not metrics)")
    c0 = clock.seconds
    eng_1, a_1, s_1 = train(x)
    print(f"one chip: K={int(eng_1.pool.count)}; pass seconds "
          f"{[round(s, 3) for s in s_1]}; compile {clock.seconds - c0:.1f} s "
          "(informational, not metrics)")
    k = int(eng_1.pool.count)
    check(k == int(eng_m.pool.count), f"sharded K == one-chip K ({k})")
    check(np.array_equal(np.asarray(eng_m.pool.centers[:k]),
                         np.asarray(eng_1.pool.centers[:k])),
          "sharded centers bit-identical to one chip")
    check(np.array_equal(np.asarray(a_m), np.asarray(a_1)),
          f"sharded assignments of all {n} points identical to one chip")
    check(engine_pass_has_kernel(eng_m, x[:n // N_BATCHES], mesh=mesh),
          "Pallas propose kernel in the compiled sharded pass")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh-sharded OCC pass against the "
                         "same pass on one chip")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    import jax

    devs = jax.devices()
    dev = devs[0]
    print(f"devices: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}; compile cache {cache}")
    if dev.platform != "tpu":
        print(f"FAIL: no TPU — JAX found platform {dev.platform!r}")
        return 1
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        (four_chips if args.chips == 4 else one_chip)(args, clock)
    except SmokeFailure as e:
        print(f"FAIL: {e}")
        return 1
    print(f"all checks passed in {time.perf_counter() - t0:.1f} s, "
          f"{clock.seconds:.1f} s of it compiling (informational)")
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
