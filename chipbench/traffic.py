"""The one general traffic generator.  A mix file (`mixes/<traffic>.json`)
is data: its `shape` says which of the two generators below reads it, and
the rest are that generator's parameters.

  jobs       repeated clustering jobs through `OCCEngine.partial_fit` /
             `flush`: the data is generated once (`job_data`), one whole
             job runs in set-up (it compiles every shape the job uses), and
             the window repeats the same job on a fresh engine, whole jobs
             only, starting none after `--seconds`.
  open_loop  independent requests through `ClusterService.submit`, each due
             at a time fixed in advance from the seed; sender threads wait
             for a request's due time and block in `submit`, and each
             request is timed from when it was due.

Both return a `Run`: the end-to-end readings, the counters the per-layer
readers take, and the numbers `correct` is decided by.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import threading
import time
from contextlib import nullcontext

import numpy as np

import common
import reference


@dataclasses.dataclass
class Run:
    end_to_end: dict            # name -> value (host clock)
    counters: dict              # what the per-layer readers read
    checks: list                # (name, value, limit, ok)
    attempted: int
    failed: int
    setup_parts: dict           # set-up split, seconds
    traced: str | None = None   # directory of the trace (--trace 1)


def _span(on: bool):
    import jax
    if on:
        return lambda name: jax.profiler.TraceAnnotation(name)
    return lambda name: nullcontext()


def _trace_options():
    import jax
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 1
    return o


def _limit_checks(readings: dict, limits: dict) -> list:
    out = []
    for name, value in readings.items():
        lim = limits[name]
        out.append((name, value, lim, bool(value <= lim)))
    return out


# ------------------------------------------------------------------ jobs

def _mesh(devs, mix):
    import jax
    if len(devs) == 1:
        return None
    return jax.make_mesh((len(devs),), (mix.get("mesh_axis", "data"),),
                         devices=devs,
                         axis_types=(jax.sharding.AxisType.Auto,))


def _one_job(x, cfg, mix, mesh, trace_at=None):
    """One clustering job: a fresh engine publishing into a fresh store,
    `chunk_points` per `partial_fit`, then `flush`.  Returns the answer
    (per-call assign / send lists, final pool) and its counters.  With
    `trace_at` set, the profiler records that one `partial_fit` call."""
    import jax
    from repro.core import DPMeansTransaction, OCCEngine
    from repro.serving import SnapshotStore

    store = SnapshotStore()
    eng = OCCEngine(DPMeansTransaction(cfg["lam"], k_max=cfg["k_max"]),
                    pb=cfg["pb"], validate_cap=cfg["validate_cap"],
                    mesh=mesh, publish=store.publish_pass)
    chunk = mix["chunk_points"]
    n_calls = -(-x.shape[0] // chunk)
    if trace_at is not None:
        trace_at %= n_calls
    assigns, sends, traced = [], [], None
    for i, lo in enumerate(range(0, x.shape[0], chunk)):
        on = i == trace_at
        span = _span(on)
        if on:
            k0 = 0 if eng.pool is None else int(eng.pool.count)
            tdir = common.trace_dir(mix["_cell"])
            jax.profiler.start_trace(tdir, profiler_options=_trace_options())
            t0 = common.now()
        with span("bench.window"):
            with span("bench.partial_fit"):
                res = eng.partial_fit(x[lo:lo + chunk])
            with span("bench.pull"):
                jax.block_until_ready(res.assign)
        if on:
            t1 = common.now()
            jax.profiler.stop_trace()
            traced = {"dir": tdir, "seconds": t1 - t0, "k_start": k0,
                      "accepted": np.asarray(res.stats.accepted),
                      "proposed": np.asarray(res.stats.proposed)}
        assigns.append(res.assign)
        sends.append(res.send)
    res = eng.flush()
    if res is not None:
        assigns.append(res.assign)
        sends.append(res.send)
    answer = (assigns, sends, eng.pool)
    return answer, {"cap_retries": eng.n_cap_retries,
                    "epochs": int(eng.stats.accepted.shape[0]),
                    "traced": traced}


def job_data(cfg, mix, seed: int):
    """The job's points on the device.  The mixture is drawn from the mix's
    fixed `base_seed`, and `seed` turns it by an orthogonal matrix: every
    seed gets the same clustering problem (the same K, adaptive caps and
    validator load, so the same work) in different numbers."""
    _, x = common.mixture(mix["base_seed"], cfg["n_points"],
                          cfg["n_components"], cfg["dim"], cfg["noise"])
    return common.rotated(seed, x)


def _fit(a, n: int, fill):
    """An answer array cut or padded to n entries: a missing answer reads
    as `fill`, which the check counts as broken."""
    import jax.numpy as jnp
    if a.shape[0] >= n:
        return a[:n]
    return jnp.concatenate([a, jnp.full((n - a.shape[0],), fill, a.dtype)])


def run_jobs(cfg, mix, cell, seed, seconds, trace, devs, clock, t_start,
             limits):
    import jax
    import jax.numpy as jnp

    mix = dict(mix, _cell=cell["name"])
    parts = {}
    t = common.now()
    x = job_data(cfg, mix, seed)
    jax.block_until_ready(x)
    parts["data_s"] = common.now() - t
    mesh = _mesh(devs, mix)

    t = common.now()
    c0 = clock.seconds
    _one_job(x, cfg, mix, mesh)
    parts["warmup_s"] = common.now() - t
    parts["compile_s"] = clock.seconds - c0

    answers, job_secs, retries, traced = [], [], 0, None
    counters = {"epochs": 0}
    clock.mark()
    t0 = common.now()
    setup_s = t0 - t_start
    while True:
        trace_at = mix.get("trace_call", -1) if trace and not job_secs \
            else None
        tj = common.now()
        answer, jc = _one_job(x, cfg, mix, mesh, trace_at)
        job_secs.append(common.now() - tj)
        if jc["traced"] is not None:
            traced = jc["traced"]
        answers.append(answer)
        retries += jc["cap_retries"]
        counters["epochs"] += jc["epochs"]
        if common.now() - t0 >= seconds:
            break
    compile_s, compiles = clock.since_mark()
    n_jobs = len(job_secs)
    window = sum(job_secs)
    counters.update(compiles=compiles, compile_s=compile_s,
                    cap_retries=retries, jobs=n_jobs,
                    points=n_jobs * cfg["n_points"], window_s=window,
                    pb=cfg["pb"], dim=cfg["dim"], chips=len(devs),
                    traced_call=traced)
    e2e = {"train_points_per_s": n_jobs * cfg["n_points"] / window,
           "setup_s": setup_s}
    common.log(f"window: {n_jobs} jobs in {window:.4f} s "
               f"(job seconds {[round(s, 4) for s in job_secs]}); "
               f"compiles in window {compiles}; cache loads in window "
               f"{clock.loads_since_mark()}; cap retries {retries}")
    peak = common.memory_peak(devs)

    # -- correctness, after the window: the job the seed picks is judged
    # by the reference; every other job must give the same answer exactly.
    rng = np.random.default_rng(seed % (1 << 63))
    pick = int(rng.integers(n_jobs))
    n = cfg["n_points"]
    flat = [(_fit(jnp.concatenate(a), n, -1), _fit(jnp.concatenate(s), n,
                                                    False), p)
            for a, s, p in answers]
    del answers
    a_ref, s_ref, p_ref = flat[pick]
    differ = sum(1 for a, s, p in flat
                 if not (bool(jnp.array_equal(a, a_ref))
                         and bool(jnp.array_equal(s, s_ref))
                         and int(p.count) == int(p_ref.count)
                         and bool(jnp.array_equal(p.centers,
                                                  p_ref.centers))))
    del flat
    t = common.now()
    readings = reference.check_job(x, p_ref.centers, p_ref.count, a_ref,
                                   s_ref, pb=cfg["pb"], lam=cfg["lam"],
                                   block=mix.get("check_block", 1024))
    readings["jobs_differing"] = differ
    common.log(f"reference check of job {pick} of {n_jobs}: "
               f"{common.now() - t:.3f} s; K={int(p_ref.count)}")
    checks = _limit_checks(readings, limits)
    counters["memory_peak_bytes"] = peak
    counters["k_final"] = int(p_ref.count)
    return Run(e2e, counters, checks, attempted=n_jobs, failed=0,
               setup_parts=parts,
               traced=None if traced is None else traced["dir"])


# ------------------------------------------------------------- open loop

def fixed_multiset(n: int, weights: dict, rng) -> np.ndarray:
    """n values holding each key of `weights` in its share (largest
    remainders), shuffled by `rng`: every seed gets the same multiset."""
    keys = list(weights)
    w = np.array([weights[k] for k in keys], float)
    w = w / w.sum() * n
    counts = np.floor(w).astype(int)
    for i in np.argsort(-(w - counts))[:n - counts.sum()]:
        counts[i] += 1
    vals = np.concatenate([np.full(c, i) for i, c in enumerate(counts)])
    rng.shuffle(vals)
    return np.array(keys, dtype=object)[vals]


def schedule(seed: int, mix: dict, seconds: float):
    """Due times (s from the window's start), kinds and rows per request.
    The gaps are the n quantiles of an exponential of mean 1/rate, shuffled:
    Poisson-like arrivals whose total work is the same for every seed."""
    rate = float(mix["rate_per_s"])
    n = int(round(rate * seconds))
    rng = np.random.default_rng(seed % (1 << 63))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    rng.shuffle(gaps)
    due = np.cumsum(gaps) - gaps[0]
    kinds = fixed_multiset(n, mix["kinds"], rng)
    rows = fixed_multiset(n, {int(k): v for k, v in mix["rows"].items()},
                          rng).astype(int)
    return due, kinds, rows


def query_rows(seed: int, means, n_rows: int, cfg: dict, mix: dict):
    """Query points on the device: components drawn Zipf(s) over a
    seed-permuted ranking of the mixture's components."""
    import jax
    import jax.numpy as jnp

    s = float(mix["zipf_s"])

    @jax.jit
    def draw(key, means):
        kp, kz, ke = jax.random.split(key, 3)
        k = means.shape[0]
        perm = jax.random.permutation(kp, k)
        logits = -s * jnp.log(jnp.arange(1, k + 1, dtype=jnp.float32))
        r = jax.random.categorical(kz, logits, shape=(n_rows,))
        return common.mixture_points(ke, means, perm[r], cfg["noise"])

    # `means` goes in as an argument: captured, it would become a constant
    # of the program, and every seed a new program to compile.
    return draw(jax.random.fold_in(common.seed_key(seed), 1), means)


def _index(seed, cfg):
    """The served index, made by the benchmark: the mixture's component
    means (one list per component, as a trained coarse quantizer has)."""
    import jax
    import jax.numpy as jnp
    from repro.core.occ import CenterPool

    k, k_max, d = cfg["n_components"], cfg["k_max"], cfg["dim"]

    @jax.jit
    def build(key):
        means = common.unit_means(jax.random.split(key, 3)[0], k, d)
        centers = jnp.zeros((k_max, d), jnp.float32).at[:k].set(means)
        return means, centers

    means, centers = build(common.seed_key(seed))
    pool = CenterPool(centers, jnp.arange(k_max) < k,
                      jnp.asarray(k, jnp.int32), jnp.asarray(False))
    return means, pool


def _warm_service(svc, rows_np, mix, cfg):
    """Compile every program the window's traffic reaches: each (kind,
    bucket) step through `submit`, and the eager concatenations the
    admission queue makes when it groups 1..bucket requests of one of the
    mix's row counts and pads a group to its bucket.  (Groups that mix row
    counts would each need a program of their own: a mix with more than
    one row count compiles inside its window.)"""
    import jax.numpy as jnp
    from repro.serving import Query

    d = cfg["dim"]
    bucket = mix["service"]["coalesce_bucket"]
    sizes = sorted(int(r) for r in mix["rows"])
    lo = 8
    for kind in mix["kinds"]:
        k = mix["k"] if kind == "topk" else 0
        b = lo
        while b <= bucket:
            svc.submit(Query(rows_np[:b], kind=kind, k=k))
            b *= 2
    for size in sizes:
        part = jnp.asarray(rows_np[:size])
        for m in range(1, bucket // size + 1):
            x = jnp.concatenate([part] * m, 0) if m > 1 else part
            n = x.shape[0]
            bk = max(lo, 1 << (n - 1).bit_length())
            if n < bk:
                jnp.concatenate([x, jnp.zeros((bk - n, d), x.dtype)], 0)


def run_open_loop(cfg, mix, cell, seed, seconds, trace, devs, clock, t_start,
                  limits):
    import jax
    import jax.numpy as jnp
    from repro.serving import ClusterService, Query, ServeConfig, SnapshotStore

    parts = {}
    t = common.now()
    means, pool = _index(seed, cfg)
    due, kinds, rows = schedule(seed, mix, seconds)
    n_req = len(due)
    n_rows = int(rows.sum())
    q_dev = query_rows(seed, means, n_rows, cfg, mix)
    q_np = np.asarray(q_dev)
    offs = np.concatenate([[0], np.cumsum(rows)])
    store = SnapshotStore()
    store.publish_pool(pool)
    parts["data_s"] = common.now() - t

    t = common.now()
    c0 = clock.seconds
    scfg = ServeConfig(**mix["service"])
    warm = ClusterService(store, scfg)
    _warm_service(warm, q_np, mix, cfg)
    warm.close()
    parts["warmup_s"] = common.now() - t
    parts["compile_s"] = clock.seconds - c0

    svc = ClusterService(store, scfg)
    k = int(mix["k"])
    queries = [Query(q_np[offs[i]:offs[i + 1]], kind=str(kinds[i]),
                     k=k if kinds[i] == "topk" else 0,
                     priority=mix.get("lane", "interactive"),
                     max_staleness=int(mix.get("max_staleness", 0)))
               for i in range(n_req)]
    late = np.full(n_req, np.nan)
    lat = np.full(n_req, np.inf)
    resp = [None] * n_req
    nxt = [0]
    lock = threading.Lock()
    span = _span(trace)
    traced = None
    if trace:
        tdir = common.trace_dir(cell["name"])
        jax.profiler.start_trace(tdir, profiler_options=_trace_options())

    def sender():
        while True:
            with lock:
                i = nxt[0]
                nxt[0] += 1
            if i >= n_req:
                return
            t_due = t0 + due[i]
            wait = t_due - common.now()
            if wait > 0:
                with span("bench.sleep"):
                    time.sleep(wait)
            t_send = common.now()
            late[i] = t_send - t_due
            try:
                with span("bench.submit"):
                    r = svc.submit(queries[i])
            except Exception as e:          # counts as missing every limit
                common.log(f"request {i} failed: {e!r}")
                continue
            lat[i] = common.now() - t_due
            resp[i] = r

    threads = [threading.Thread(target=sender, daemon=True)
               for _ in range(int(mix["threads"]))]
    # The generator's own objects (every request, made in set-up) would
    # otherwise be scanned by each full collection inside the window.
    gc.collect()
    gc.freeze()
    clock.mark()
    t0 = common.now() + 0.05
    setup_s = t0 - t_start
    with span("bench.window"):
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    gc.unfreeze()
    if trace:
        jax.profiler.stop_trace()
        traced = tdir
    compile_s, compiles = clock.since_mark()
    svc_metrics = svc.metrics()
    svc.close()
    ok = np.isfinite(lat)
    n_ok = int(ok.sum())
    done_t = t0 + np.where(ok, due + lat, 0)
    window = max(float(done_t.max()) - t0, seconds) if n_ok else seconds
    e2e = {"serve_p95_ms": 1e3 * common.quantile(list(lat), 0.95),
           "serve_qps": n_ok / window,
           "setup_s": setup_s}
    late_ms = 1e3 * late[np.isfinite(late)]
    groups = {}
    for i in np.nonzero(ok)[0]:
        r = resp[i]
        g = r.group if r.group >= 0 else -1 - int(i)
        groups[g] = groups.get(g, 0) + int(rows[i])
    counters = {
        "compiles": compiles, "compile_s": compile_s, "window_s": window,
        "requests": n_req, "rows": int(rows[ok].sum()),
        "group_rows": list(groups.values()),
        "bucket_fill": svc_metrics["bucket_fill_ratio"],
        "dispatches": svc_metrics["n_dispatches"],
        "late_p95_ms": float(np.percentile(late_ms, 95)),
        "late_max_ms": float(late_ms.max()),
        "stalls": [(round(float(due[i]), 3), round(1e3 * float(late[i]), 1))
                   for i in np.argsort(-np.nan_to_num(late))[:5]],
        "n_centers": int(pool.count), "dim": cfg["dim"],
        "chips": len(devs), "latencies_s": lat}
    common.log(
        f"window: {n_req} requests due over {float(due[-1]):.3f} s, "
        f"{n_ok} answered by {window:.4f} s; p50 "
        f"{1e3 * common.quantile(list(lat), 0.5):.3f} ms, p95 "
        f"{e2e['serve_p95_ms']:.3f} ms, p99 "
        f"{1e3 * common.quantile(list(lat), 0.99):.3f} ms; sender late p95 "
        f"{counters['late_p95_ms']:.3f} ms, max {counters['late_max_ms']:.3f}"
        f" ms; bucket fill {counters['bucket_fill']:.4f}; compiles in "
        f"window {compiles}; latest sends (due s, late ms) "
        f"{counters['stalls']}; cache loads in window {clock.loads_since_mark()}")
    counters["memory_peak_bytes"] = common.memory_peak(devs)

    # -- correctness, after the window: every answered row against the
    # reference.  Rows are grouped by kind; a missing answer is a failure.
    t = common.now()
    bad, gap, err = 0, -math.inf, -math.inf
    block = int(mix.get("check_block", 1024))
    for kind in mix["kinds"]:
        kk = k if kind == "topk" else 1
        sel = [i for i in range(n_req) if kinds[i] == kind]
        idx_rows = np.concatenate([np.arange(offs[i], offs[i + 1])
                                   for i in sel])
        ids = np.full((len(idx_rows), kk), -1, np.int32)
        sc = np.full((len(idx_rows), kk), np.inf, np.float32)
        pos = 0
        for i in sel:
            r, m = resp[i], rows[i]
            if r is not None:
                ids[pos:pos + m] = np.asarray(r.labels).reshape(m, kk)
                sc[pos:pos + m] = np.asarray(r.scores).reshape(m, kk)
            pos += m
        pad = (-len(idx_rows)) % block
        qk = jnp.asarray(np.concatenate(
            [q_np[idx_rows], np.zeros((pad, q_np.shape[1]), np.float32)]))
        ids = np.concatenate([ids, np.full((pad, kk), -2, np.int32)])
        sc = np.concatenate([sc, np.zeros((pad, kk), np.float32)])
        b, g, e = reference.answer_gaps(qk, pool.centers, pool.count,
                                        jnp.asarray(ids), jnp.asarray(sc),
                                        block=block)
        bad += int(b)
        gap = max(gap, float(g))
        err = max(err, float(e))
    readings = {"answers_bad": bad,
                "rank_gap": gap, "score_err": err}
    common.log(f"reference check of {n_rows} rows: "
               f"{common.now() - t:.3f} s")
    checks = _limit_checks(readings, limits)
    return Run(e2e, counters, checks, attempted=n_req, failed=n_req - n_ok,
               setup_parts=parts, traced=traced)


SHAPES = {"jobs": run_jobs, "open_loop": run_open_loop}
