"""OCC online facility location (paper Alg. 4/5): the program's
`OFLTransaction`, judged by the readings of `reference.py` with a threshold
per point.  A point opens a facility iff u_i < min(1, d²/λ²), that is iff
d² > t_i = λ²·u_i, so OFL's rule is DP-means' with t_i in place of λ²
(`reference_ofl.py`): the check redraws every u_i from the configuration's
`uniform_seed` and reads the answer with `reference.job_structure`,
`job_margins` and `job_readings` given those t_i.  A job's answer is, per
point, its facility (N,) int32 and whether it was sent (N,) bool, and the
pool of facilities.  The control recomputes every assignment and send
decision one precision step down (`reference_ofl.control_answers`).

The pool's capacity (`k_max`) is far above what a job opens, so the check
measures only the live prefix of the pool, rounded up to `LIVE_TILE`, in
row blocks of at most `BLOCK_ELEMS` distances.
"""
import jax.numpy as jnp

import common
import flops
import reference
import reference_ofl

# The propose is the nearest-facility kernel DP-means runs.
PROPOSE_KERNEL = "dpmeans_assign"
# The check's center count is the live count rounded up to this, so that
# seeds whose K differs by a little share one compiled check.
LIVE_TILE = 16384
# Distances in one row block of the check: 2^27 float32, 512 MiB.
BLOCK_ELEMS = 1 << 27


def transaction(cfg, seed):
    """The key comes from the configuration's `uniform_seed`, not from the
    run's seed: every seed poses the same problem (the data is turned by
    the seed's rotation, `traffic.job_data`), so K is the same on every
    seed, while each seed's numbers are new."""
    from repro.core import OFLTransaction
    return OFLTransaction(cfg["lam"], cfg["k_max"],
                          common.seed_key(cfg["uniform_seed"]))


def _judged(x, answer, cfg, mix):
    """(thresholds, live centers, row block) the readings are taken with."""
    assign, send, pool = answer
    n = x.shape[0]
    u = reference_ofl.uniforms(common.seed_key(cfg["uniform_seed"]), n)
    count = int(pool.count)
    k = min(pool.centers.shape[0], -(-max(count, 1) // LIVE_TILE) * LIVE_TILE)
    block = int(mix.get("check_block", 1024))
    while block > 8 and (block * k > BLOCK_ELEMS or n % block):
        block //= 2
    return reference_ofl.thresholds(u, cfg["lam"]), pool.centers[:k], block


def _readings(x, assign, send, count, centers, t, pb, block):
    bad, creator, avail, k_start = reference.job_structure(
        x, centers, count, assign, send, pb=pb)
    m_av, m_st, d_as = reference.job_margins(x, centers, assign, avail,
                                             k_start, block=block)
    gap, rule = reference.job_readings(assign, send, creator, avail, m_av,
                                       m_st, d_as, t)
    return {"answers_bad": int(bad), "assign_gap": float(gap),
            "rule_gap": float(rule)}


def check(x, answer, cfg, mix):
    assign, send, pool = answer
    t, centers, block = _judged(x, answer, cfg, mix)
    return _readings(x, assign, send, pool.count, centers, t, cfg["pb"],
                     block)


def control(x, answer, cfg, mix, dtype):
    """`check` of the control's answers: the program's pool, with every
    non-creator's facility and every send decision made in `dtype`."""
    assign, send, pool = answer
    t, centers, block = _judged(x, answer, cfg, mix)
    _, creator, avail, k_start = reference.job_structure(
        x, centers, pool.count, assign, send, pb=cfg["pb"])
    a_c, s_c = reference_ofl.control_answers(x, centers, avail, k_start, t,
                                             block=block, dtype=dtype)
    a_c = jnp.where(creator, assign, a_c)
    s_c = jnp.where(creator, True, s_c)
    return _readings(x, a_c, s_c, pool.count, centers, t, cfg["pb"], block)


def propose_work(k_start, accepted, pb, d):
    """The nearest-facility propose: DP-means' count, each of the epoch's
    pb points against the K_e facilities open at its start."""
    f, b = flops.propose_epochs([k_start] + list(accepted), pb, d)
    return f[1:], b[1:]


def small(cfg):
    """The configuration at a size the CPU runs in seconds: its width and
    λ, fewer components, points and slots, and more noise, so that a
    component holds several facilities and, as at full size, about a
    quarter of the points open one."""
    return dict(cfg, n_components=256, n_points=16384, k_max=8192,
                noise=0.8, pb=min(cfg["pb"], 256))


# ------------------------------------------- faults planted by the tests

def _uniforms_by_call(monkeypatch):
    """Each call's uniforms drawn from index 0 of the call instead of the
    point's index in the job: every call after the first redraws its
    points' uniforms for the wrong indices."""
    from repro.core import OFLTransaction
    orig = OFLTransaction.make_state

    def make_state(self, x, offset=0):
        return orig(self, x, 0)

    monkeypatch.setattr(OFLTransaction, "make_state", make_state)


def _send_against_lam2(monkeypatch):
    """A point sent iff it lies farther than λ from the epoch's start pool,
    as DP-means sends, instead of farther than λ·√u_i.  The rule is traced
    into the compiled pass, so it comes as a transaction class of its own,
    which compiles a pass of its own."""
    import jax
    import repro.core

    @jax.tree_util.register_pytree_node_class
    class SendAgainstLam2(repro.core.OFLTransaction):
        def propose(self, pool, x_e, u_e):
            send, payload, aux, idx = super().propose(pool, x_e, u_e)
            d2 = aux[1]
            return d2 > self._lam2(d2.dtype), payload, aux, idx

    monkeypatch.setattr(repro.core, "OFLTransaction", SendAgainstLam2)


def _dropped_facility(monkeypatch):
    """The last facility each call opens dropped from the pool: its slot
    is freed, and the next facility opened takes it."""
    from repro.core.engine import OCCEngine
    orig = OCCEngine._commit_stream_pass

    def commit(self, xb, state):
        res = orig(self, xb, state)
        p = self._pool
        last = p.count - 1
        p = p._replace(centers=p.centers.at[last].set(0.0),
                       mask=p.mask.at[last].set(False), count=last)
        self._pool = p
        return res._replace(pool=p)

    monkeypatch.setattr(OCCEngine, "_commit_stream_pass", commit)


FAULTS = (_uniforms_by_call, _send_against_lam2, _dropped_facility)
