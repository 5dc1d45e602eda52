"""Multi-process OCC: sharded propose workers + serializing master (§13).

The paper's P-machine experiment as real OS processes.  A master process
drives `OCCEngine.run_from_proposals`; P spawned worker processes each
hold a bit-exact replica of the center pool (tailed from the master's
per-epoch DELTA broadcasts) and run the optimistic `propose` phase on a
disjoint contiguous shard of every epoch.  Proposal blocks stream back as
PROPOSE frames; the master reassembles them in worker order (== global
index order), runs the ONE true precomputed validator, commits the epoch,
and publishes the pool delta — to the workers (training plane) and to any
number of socket-connected follower stores via `ReplicationServer`
(replication plane, with acks and snapshot bootstrap for late joiners).

Because a jitted shard-shaped `propose` equals the matching slice of the
jitted full-epoch `propose`, and the master's per-epoch finish equals the
fused scan's epoch body, the whole multi-process run is **bit-identical**
to the single-process `OCCEngine.run` on the same data — final centers,
per-point assignments, `OCCStats`, and every follower's snapshot store.
The driver audits all of that and emits BENCH_transport.json (delta
bytes/publish, replication ack latency p50/p99).

Failure semantics (chaos-tested in tests/test_occ_cluster.py):
  * a worker that dies mid-epoch is detected by socket EOF (belt:
    `fault.HeartbeatTracker` timeout for hangs); its shard is masked
    invalid from that epoch on and the master completes every epoch with
    the survivors' proposals — deterministically, because the dead
    worker's points are excluded exactly from the epoch whose STEP it
    never answered;
  * a follower killed mid-publish simply drops off the ack set; a
    replacement follower bootstraps from a SNAPSHOT frame and tails to
    the same bit-identical store.

This is a loopback emulation of hosts on the CPU, not a chip path: every
process (master, workers, followers) pins JAX to the CPU and prints the
platform it got — a spawned child that reached for the TPU would contend
with its parent for the one chip.

  PYTHONPATH=src python -m repro.launch.occ_cluster [--quick] \
      --workers 2 --followers 1 --out BENCH_transport.json
"""
from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import socket
import tempfile
import threading
import time
from dataclasses import dataclass

import numpy as np

__all__ = ["ClusterConfig", "run_cluster", "worker_main"]


@dataclass
class ClusterConfig:
    n: int = 4096
    dim: int = 16
    lam: float = 4.0
    k_max: int = 256
    pb: int = 128               # points per epoch (split across workers)
    n_workers: int = 2
    n_followers: int = 1        # followers connected before epoch 0
    validate_cap: int | None = None
    seed: int = 0
    model: str = "occ"
    snapshot_capacity: int = 256    # ring >= epochs+1: version lists compare
    late_follower: bool = True      # spawn one follower mid-run (bootstrap)
    late_join_frac: float = 0.5     # ...after this fraction of the epochs
    worker_timeout_s: float = 120.0  # heartbeat timeout (EOF detects deaths)
    spawn_timeout_s: float = 120.0   # worker connect + follower join budget
    straggler_threshold: float = 3.0  # epoch slower than this x EWMA → event
    straggler_warmup: int = 3        # ignore compile-dominated first epochs
    # chaos knobs (tests/test_occ_cluster.py pins their outcomes)
    die_worker: int | None = None    # this worker exits without proposing...
    die_epoch: int | None = None     # ...upon receiving STEP for this epoch
    kill_follower_at_epoch: int | None = None  # SIGKILL follower 0 here and
    #                                            respawn a fresh one after
    out_path: str | None = None
    trace_out: str | None = None    # master-side Perfetto JSON
    quiet: bool = False


def _cluster_data(cfg: ClusterConfig):
    """Deterministic per-config dataset — every process regenerates the
    same points from (n, seed, dim), so no training data travels on the
    wire (shards are index ranges, exactly the paper's setup)."""
    import jax.numpy as jnp
    from repro.data import dp_stick_breaking_data
    x, _, _ = dp_stick_breaking_data(cfg.n, seed=cfg.seed, dim=cfg.dim)
    return jnp.asarray(x)


def _cluster_txn(cfg: ClusterConfig):
    from repro.core.dp_means import DPMeansTransaction
    return DPMeansTransaction(cfg.lam, cfg.k_max)


def _padded_epochs(cfg: ClusterConfig, x, state):
    """(x, valid, state) padded to t*pb — the engine's exact epoch
    partition, recomputed identically by master and every worker."""
    import jax
    import jax.numpy as jnp
    from repro.core.occ import block_epochs
    n = x.shape[0]
    t = block_epochs(n, cfg.pb)
    pad = t * cfg.pb - n
    zp = lambda a: jnp.concatenate(
        [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)], 0)
    return t, zp(x), jax.tree.map(zp, state)


# --------------------------------------------------------------- worker side

def _serve_master(sock: socket.socket, cfg: ClusterConfig, worker_id: int,
                  txn, xp, sp, replica: dict) -> str:
    """Serve ONE master connection until FIN ("fin") or a broken stream
    ("eof" — the §14 orphaned signal for the HA worker's reconnect loop).

    `replica` (centers ndarray / count / term) persists across calls so a
    reconnecting HA worker keeps its pool between masters; a promoted
    master's first broadcast is a rebase delta that resets it anyway.
    Term fencing (§14): DELTA/SNAPSHOT/STEP frames below the replica's
    known term are zombie-master traffic and are ignored outright.
    """
    import jax
    import jax.numpy as jnp
    from repro.core.engine import _propose_epoch_jit
    from repro.core.occ import CenterPool
    from repro.distributed.protocol import (
        DELTA, FIN, SNAPSHOT, STEP, frame_delta, propose_frame,
        read_frame, write_frame)

    spb = cfg.pb // cfg.n_workers
    centers = replica["centers"]
    try:
        while True:
            fr = read_frame(sock)
            if fr is None:
                return "eof"
            ftype, meta, arrays = fr
            if ftype in (DELTA, SNAPSHOT, STEP):
                term = int(meta.get("term", 0))
                if term < replica["term"]:
                    continue            # §14 fencing: stale-term frame
                replica["term"] = term
            if ftype in (DELTA, SNAPSHOT):
                delta = frame_delta(meta, arrays)
                if delta.rebase:
                    centers[:] = 0.0
                    replica["count"] = 0
                assert delta.start == replica["count"], \
                    "pool delta gap at worker"
                centers[delta.start:delta.count] = delta.rows
                replica["count"] = delta.count
            elif ftype == STEP:
                e = int(meta["epoch"])
                if cfg.die_epoch == e and cfg.die_worker == worker_id:
                    os._exit(3)          # hard mid-epoch death, no FIN
                count = replica["count"]
                assert int(meta["count"]) == count, "replica out of sync"
                pool = CenterPool(
                    jnp.asarray(centers),
                    jnp.arange(cfg.k_max) < count,
                    jnp.asarray(count, jnp.int32), jnp.asarray(False))
                cut = slice(e * cfg.pb + worker_id * spb,
                            e * cfg.pb + (worker_id + 1) * spb)
                out = _propose_epoch_jit(
                    txn, pool, xp[cut], jax.tree.map(lambda s: s[cut], sp))
                leaves = [np.asarray(l) for l in jax.tree_util.tree_leaves(out)]
                write_frame(sock, propose_frame(e, worker_id, leaves))
            elif ftype == FIN:
                return "fin"
    except (ConnectionError, OSError):
        return "eof"
    finally:
        sock.close()


def cpu_host(role: str) -> None:
    """Pin this emulated host's process to the CPU and print its platform.
    Spawned children inherit the setting before they import JAX; the config
    update covers a process that imported JAX before calling this."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    print(f"[{role}] pid {os.getpid()}: JAX platform "
          f"{jax.devices()[0].platform}", flush=True)


def worker_main(cfg_kw: dict, worker_id: int, port: int) -> None:
    """One propose worker (spawned process): tail pool deltas, answer STEP
    frames with the jitted shard propose, exit on FIN.

    The pool replica is rebuilt from broadcast deltas only — the worker
    never sees the master's pool object, yet proposes against bit-equal
    state C^{t-1} (append-only pool + prefix mask ⇒ the replica IS the
    pool).  If cfg.die_epoch targets this worker it exits hard (os._exit)
    upon the STEP, before proposing — the chaos tests' mid-epoch death.
    """
    cpu_host(f"occ worker {worker_id}")
    from repro.distributed.protocol import hello_frame, write_frame

    cfg = ClusterConfig(**cfg_kw)
    x = _cluster_data(cfg)
    txn = _cluster_txn(cfg)
    state = txn.make_state(x, 0)
    _, xp, sp = _padded_epochs(cfg, x, state)
    replica = dict(centers=np.zeros((cfg.k_max, cfg.dim), np.float32),
                   count=0, term=0)
    sock = socket.create_connection(("127.0.0.1", port), timeout=30.0)
    sock.settimeout(None)
    write_frame(sock, hello_frame("worker", cfg.model, worker=worker_id))
    _serve_master(sock, cfg, worker_id, txn, xp, sp, replica)


# --------------------------------------------------------------- master side

class _WorkerPlane:
    """Master end of the training plane: P worker sockets, a reader thread
    per worker filling the per-epoch inbox, EOF + heartbeat liveness."""

    def __init__(self, cfg: ClusterConfig):
        from repro.distributed.fault import HeartbeatTracker
        self.cfg = cfg
        self.lsock = socket.create_server(("127.0.0.1", 0))
        self.port = self.lsock.getsockname()[1]
        self.conns: dict[int, socket.socket] = {}
        self.alive = [True] * cfg.n_workers
        self.inbox: dict[tuple[int, int], list[np.ndarray]] = {}
        self.cv = threading.Condition()
        self.hb = HeartbeatTracker(timeout=cfg.worker_timeout_s)
        self.procs: list[mp.process.BaseProcess] = []
        self._readers: list[threading.Thread] = []

    def spawn(self) -> None:
        ctx = mp.get_context("spawn")
        cfg_kw = {**self.cfg.__dict__, "out_path": None}
        for w in range(self.cfg.n_workers):
            p = ctx.Process(target=worker_main, args=(cfg_kw, w, self.port),
                            daemon=True)
            p.start()
            self.procs.append(p)
        self.accept_workers()

    def accept_workers(self) -> None:
        """Accept `n_workers` HELLO handshakes — from children this plane
        spawned, or from §14 HA workers reconnecting to a promoted master
        (the plane does not care who forked them)."""
        from repro.distributed.protocol import HELLO, read_frame
        self.lsock.settimeout(self.cfg.spawn_timeout_s)
        for _ in range(self.cfg.n_workers):
            sock, _addr = self.lsock.accept()
            sock.settimeout(None)
            fr = read_frame(sock)
            assert fr is not None and fr[0] == HELLO, "bad worker handshake"
            wid = int(fr[1]["worker"])
            self.conns[wid] = sock
            self.hb.beat(wid)
            t = threading.Thread(target=self._reader, args=(wid, sock),
                                 name=f"worker-rx-{wid}", daemon=True)
            t.start()
            self._readers.append(t)

    def _reader(self, wid: int, sock: socket.socket) -> None:
        from repro.distributed.protocol import PROPOSE, read_frame
        try:
            while True:
                fr = read_frame(sock)
                if fr is None:
                    break
                ftype, meta, arrays = fr
                if ftype == PROPOSE:
                    leaves = [arrays[f"leaf{i}"]
                              for i in range(int(meta["n_leaves"]))]
                    with self.cv:
                        self.inbox[(int(meta["epoch"]), wid)] = leaves
                        self.hb.beat(wid)
                        self.cv.notify_all()
        except (ConnectionError, OSError, ValueError):
            pass
        with self.cv:
            self.alive[wid] = False
            self.cv.notify_all()

    def broadcast(self, frame: bytes) -> None:
        for wid, sock in self.conns.items():
            if not self.alive[wid]:
                continue
            try:
                sock.sendall(frame)
            except OSError:
                with self.cv:
                    self.alive[wid] = False
                    self.cv.notify_all()

    def gather(self, epoch: int) -> dict[int, list[np.ndarray] | None]:
        """Block until every live worker answered `epoch` (or died — EOF is
        the fast path, the heartbeat timeout the hang backstop).  Returns
        worker → leaves, None for workers dead by/at this epoch."""
        with self.cv:
            while True:
                for wid in self.hb.dead_hosts():
                    self.alive[wid] = False     # hang backstop
                missing = [w for w in range(self.cfg.n_workers)
                           if self.alive[w] and (epoch, w) not in self.inbox]
                if not missing:
                    break
                self.cv.wait(0.05)
            return {w: self.inbox.pop((epoch, w), None)
                    for w in range(self.cfg.n_workers)}

    def close(self) -> None:
        from repro.distributed.protocol import fin_frame
        self.broadcast(fin_frame("pass complete"))
        for p in self.procs:
            p.join(timeout=30.0)
        for sock in self.conns.values():
            try:
                sock.close()
            except OSError:
                pass
        self.lsock.close()


class _ClusterProposer:
    """`propose_fn` for `OCCEngine.run_from_proposals`, backed by the
    worker plane: broadcast the epoch-start pool delta + STEP, gather the
    PROPOSE blocks, reassemble leaves in worker order, mask dead shards."""

    def __init__(self, cfg: ClusterConfig, txn, plane: _WorkerPlane,
                 term: int = 0, rebase_first: bool = False):
        self.cfg = cfg
        self.txn = txn
        self.plane = plane
        self.term = term                # §14: stamped on every broadcast
        self.last_count = 0
        self._force_rebase = rebase_first   # promoted master: the workers'
        #   replicas come from a DEAD master's stream — rebase them first
        self._template = None           # (treedef, shard leaf specs)
        self.dead_from: dict[int, int] = {}   # worker → first masked epoch

    def _shard_template(self, pool, x_e, state_e):
        import jax
        spb = self.cfg.pb // self.cfg.n_workers
        cut = lambda a: a[:spb]
        sd = jax.eval_shape(self.txn.propose, pool, cut(x_e),
                            jax.tree.map(cut, state_e))
        leaves, treedef = jax.tree_util.tree_flatten(sd)
        return treedef, [(l.shape, l.dtype) for l in leaves]

    def _pool_delta(self, pool, epoch: int):
        from repro.serving.snapshot import CenterDelta
        cnp = np.asarray(pool.centers)
        count = int(pool.count)
        rebase = epoch == 0 or self._force_rebase
        self._force_rebase = False
        start = 0 if rebase else self.last_count
        self.last_count = count
        return CenterDelta(model=self.cfg.model, version=epoch, start=start,
                           rows=cnp[start:count], count=count,
                           capacity=self.cfg.k_max, rebase=rebase)

    def __call__(self, pool, x_e, state_e, valid_e, *, epoch, offset):
        import jax
        import jax.numpy as jnp
        from repro.distributed.protocol import delta_frame, step_frame
        if self._template is None:
            self._template = self._shard_template(pool, x_e, state_e)
        treedef, specs = self._template
        self.plane.broadcast(delta_frame(self._pool_delta(pool, epoch),
                                         term=self.term))
        self.plane.broadcast(step_frame(epoch, self.last_count,
                                        term=self.term))
        blocks = self.plane.gather(epoch)
        spb = self.cfg.pb // self.cfg.n_workers
        cat = []
        for i, (shape, dtype) in enumerate(specs):
            parts = []
            for w in range(self.cfg.n_workers):
                lv = blocks[w]
                parts.append(np.zeros(shape, dtype) if lv is None else lv[i])
            cat.append(jnp.asarray(np.concatenate(parts, 0)))
        send, payload, aux, safe = jax.tree_util.tree_unflatten(treedef, cat)
        dead = [w for w, lv in blocks.items() if lv is None]
        if dead:
            rows = np.ones((self.cfg.pb,), bool)
            for w in dead:
                self.dead_from.setdefault(w, epoch)
                rows[w * spb:(w + 1) * spb] = False
            valid_e = jnp.logical_and(valid_e, jnp.asarray(rows))
        return send, payload, aux, safe, valid_e


def _masked_reference(cfg: ClusterConfig, engine, dead_from: dict[int, int]):
    """The deterministic chaos oracle: the in-process proposer with the
    SAME shard masking the master applied for dead workers."""
    import jax.numpy as jnp
    base = engine.local_proposer()
    spb = cfg.pb // cfg.n_workers
    masks = {}
    for w, e0 in dead_from.items():
        rows = np.ones((cfg.pb,), bool)
        rows[w * spb:(w + 1) * spb] = False
        masks[w] = (e0, jnp.asarray(rows))

    def fn(pool, x_e, state_e, valid_e, *, epoch, offset):
        s, p, a, sf, ve = base(pool, x_e, state_e, valid_e,
                               epoch=epoch, offset=offset)
        for e0, rows in masks.values():
            if epoch >= e0:
                ve = jnp.logical_and(ve, rows)
        return s, p, a, sf, ve
    return fn


def run_cluster(cfg: ClusterConfig) -> dict:
    cpu_host("occ master")
    from repro.core.engine import OCCEngine
    from repro.distributed.transport import ReplicationServer, store_digest
    from repro.launch.occ_follower import follower_main
    from repro.obs import Obs, Tracer
    from repro.serving.snapshot import SnapshotStore

    assert cfg.pb % cfg.n_workers == 0, "pb must split evenly across workers"
    # ONE shared Obs for the master process: engine passes, replication and
    # the straggler watchdog land in one registry / one trace file.
    obs = Obs(tracer=Tracer("occ_cluster.master") if cfg.trace_out else None,
              trace_path=cfg.trace_out)
    t0 = time.perf_counter()
    x = _cluster_data(cfg)
    txn = _cluster_txn(cfg)

    # replication plane: primary store wired straight onto the socket server
    srv = ReplicationServer(obs=obs)
    store = SnapshotStore(capacity=cfg.snapshot_capacity, delta=True,
                          model=cfg.model, wire=srv)
    ctx = mp.get_context("spawn")
    tmp = tempfile.mkdtemp(prefix="occ_cluster_")
    followers: list[dict] = []      # {proc, path, late, replacement}

    def spawn_follower(late: bool, replacement: bool = False) -> None:
        path = os.path.join(tmp, f"follower_{len(followers)}.json")
        p = ctx.Process(
            target=follower_main,
            args=(srv.address[0], srv.address[1], cfg.model, path,
                  cfg.snapshot_capacity),
            daemon=True)
        p.start()
        followers.append(dict(proc=p, path=path, late=late,
                              replacement=replacement))

    for _ in range(cfg.n_followers):
        spawn_follower(late=False)
    deadline = time.monotonic() + cfg.spawn_timeout_s
    while (srv.followers(cfg.model) < cfg.n_followers
           and time.monotonic() < deadline):
        time.sleep(0.01)
    assert srv.followers(cfg.model) == cfg.n_followers, "follower connect"

    # training plane
    plane = _WorkerPlane(cfg)
    plane.spawn()
    proposer = _ClusterProposer(cfg, txn, plane)
    engine = OCCEngine(txn, pb=cfg.pb, validate_cap=cfg.validate_cap,
                       obs=obs)

    killed = {"done": False}
    # straggler watchdog on the master's epoch loop: a slow epoch (a hung
    # or lagging worker that still answers before the heartbeat timeout)
    # emits a StragglerEvent into the run's metrics instead of passing
    # silently — the observability half of §13's failure semantics.
    from repro.distributed.fault import StepWatchdog
    watchdog = StepWatchdog(threshold=cfg.straggler_threshold,
                            warmup_steps=cfg.straggler_warmup, obs=obs)
    last_commit = [time.perf_counter()]

    def on_commit(pool, epoch, t_epochs):
        now = time.perf_counter()
        watchdog.observe(epoch, now - last_commit[0])
        last_commit[0] = now
        store.publish_pool(pool, n_seen=min(cfg.n, (epoch + 1) * cfg.pb),
                           epochs=epoch + 1)
        if (cfg.kill_follower_at_epoch == epoch and not killed["done"]
                and followers):
            followers[0]["proc"].kill()      # mid-publish, no FIN, no ACK
            killed["done"] = True
            spawn_follower(late=True, replacement=True)
        if cfg.late_follower and epoch == max(1, int(t_epochs
                                                     * cfg.late_join_frac)):
            spawn_follower(late=True)

    res = engine.run_from_proposals(x, proposer, on_commit=on_commit)
    plane.close()

    # replication barrier: every surviving follower connected and acked
    latest = store.latest_meta().version
    expect = sum(1 for f in followers
                 if not (killed["done"] and f is followers[0]))
    deadline = time.monotonic() + cfg.spawn_timeout_s
    while (srv.followers(cfg.model) < expect
           and time.monotonic() < deadline):
        time.sleep(0.01)
    assert srv.wait_acked(latest, cfg.model,
                          timeout=cfg.spawn_timeout_s), "ack barrier"
    metrics = srv.metrics()
    srv.close()     # FIN → followers write their reports and exit
    reports = []
    for f in followers:
        f["proc"].join(timeout=30.0)
        if os.path.exists(f["path"]):
            with open(f["path"]) as fh:
                reports.append({**json.load(fh), "late": f["late"],
                                "replacement": f["replacement"]})

    # ------------------------------------------------------------- audit
    # The single-process oracle: the fused one-jit pass (clean run), or the
    # host-driven pass with the same dead-shard masks (chaos run).
    ref_engine = OCCEngine(txn, pb=cfg.pb, validate_cap=cfg.validate_cap)
    if proposer.dead_from:
        ref = ref_engine.run_from_proposals(
            x, _masked_reference(cfg, ref_engine, proposer.dead_from))
    else:
        ref = ref_engine.run(x)
    eq = lambda a, b: bool(np.array_equal(np.asarray(a), np.asarray(b)))
    bit = dict(
        centers=eq(ref.pool.centers, res.pool.centers),
        count=int(ref.pool.count) == int(res.pool.count),
        mask=eq(ref.pool.mask, res.pool.mask),
        assign=eq(ref.assign, res.assign),
        send=eq(ref.send, res.send),
        epoch_of=eq(ref.epoch_of, res.epoch_of),
        stats_proposed=eq(ref.stats.proposed, res.stats.proposed),
        stats_accepted=eq(ref.stats.accepted, res.stats.accepted),
        stats_cap=eq(ref.stats.cap, res.stats.cap),
    )
    primary_digest = store_digest(store)
    follower_ok = [r["digest"] == primary_digest for r in reports]
    boot_ok = all(r["bootstrapped"] for r in reports if r["late"])
    full_stream_ok = all(r["versions"] == store.versions()
                         for r in reports if not r["late"])

    record = {
        "bench": "transport",
        "n": cfg.n, "dim": cfg.dim, "pb": cfg.pb,
        "workers": cfg.n_workers,
        "followers": len(reports),
        "epochs": int(res.stats.proposed.shape[0]),
        "k_final": int(res.pool.count),
        "versions_published": len(store),
        "delta_rows_published": store.delta_rows_published,
        "delta_bytes_per_publish":
            metrics["bytes_sent"] / max(1, metrics["n_sent"]),
        "ack_p50_ms": metrics["ack_p50_ms"],
        "ack_p99_ms": metrics["ack_p99_ms"],
        "n_acks": metrics["n_acks"],
        "n_bootstraps": metrics["n_bootstraps"],
        "bit_identical": bit,
        "follower_digests_match": follower_ok,
        "late_joiners_bootstrapped": boot_ok,
        "full_stream_versions_match": full_stream_ok,
        "worker_deaths": proposer.dead_from,
        "straggler_events": [
            dict(step=ev.step, elapsed_s=ev.elapsed, ratio=ev.ratio)
            for ev in watchdog.events],
        "wall_s": time.perf_counter() - t0,
    }
    obs.flush()
    assert all(bit.values()), f"multi-process run diverged: {bit}"
    assert reports and all(follower_ok), "follower store digest mismatch"
    assert boot_ok, "a late joiner did not bootstrap from a snapshot"
    assert full_stream_ok, "a from-start follower lost versions"
    if cfg.die_worker is not None:
        assert proposer.dead_from.get(cfg.die_worker) == cfg.die_epoch, (
            "worker death not detected at the pinned epoch")
    if cfg.kill_follower_at_epoch is not None:
        rep = [r for r in reports if r["replacement"]]
        assert rep and rep[0]["bootstrapped"], "replacement did not resync"
    if cfg.out_path is not None:
        with open(cfg.out_path, "w") as f:
            json.dump(record, f, indent=2)
    if not cfg.quiet:
        print(f"{cfg.n_workers} workers x {record['epochs']} epochs over "
              f"{cfg.n} points -> K={record['k_final']} "
              f"({record['versions_published']} versions, "
              f"{record['delta_bytes_per_publish']:.0f} B/publish)")
        print(f"bit-identical to single-process pass: "
              f"{all(bit.values())}  followers={len(reports)} "
              f"(late bootstraps ok: {boot_ok})  "
              f"ack p50={record['ack_p50_ms']:.2f}ms "
              f"p99={record['ack_p99_ms']:.2f}ms")
    return record


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--pb", type=int, default=128)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--followers", type=int, default=1)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke sizes (numbers not meaningful)")
    ap.add_argument("--out", default=None,
                    help="write BENCH_transport.json here")
    ap.add_argument("--trace-out", default=None,
                    help="write a Perfetto/Chrome trace JSON here")
    args = ap.parse_args(argv)
    cfg = ClusterConfig(n=args.n, dim=args.dim, pb=args.pb,
                        n_workers=args.workers, n_followers=args.followers,
                        out_path=args.out, trace_out=args.trace_out)
    if args.quick:
        cfg = ClusterConfig(n=1024, dim=8, pb=64, k_max=128, lam=3.0,
                            n_workers=args.workers,
                            n_followers=args.followers, out_path=args.out,
                            trace_out=args.trace_out)
    run_cluster(cfg)


if __name__ == "__main__":
    main()
