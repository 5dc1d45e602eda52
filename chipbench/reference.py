"""The plain reference: what a clustering job and a served query must answer,
checked answer by answer in straightforward `jax.numpy` at float32 and
HIGHEST matmul precision.  It imports nothing of the program.

Train (OCC DP-means, paper Alg. 3 with DPValidate): the points arrive in
epochs of `pb`; a point is *sent* iff it lies farther than λ from every
center of the pool at the start of its epoch; sent points are validated in
index order and a sent point becomes a new center (itself) iff it is still
farther than λ from every center accepted before it.  A job's answer is its
pool (the centers, in creation order) and, per point, its assignment and
whether it was sent.  The check reads the answer and judges every point
against the rule, given the centers the answer says existed when that point
was decided: the creators are recovered from the assignments and each
center must equal its creator point bit for bit, so the centers the check
measures against are the benchmark's own data, never the program's numbers.

Serve (flat IVF probe): a `score` answer is the nearest center and its
squared distance; a `topk` answer is the k nearest, ascending.

Every distance here is |x|^2 + |c|^2 - 2 x.c in float32 at HIGHEST, in
blocks of rows.  `dtype=bfloat16` gives the control: the same answers
computed in one bfloat16 pass, the step below the configuration's float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def sq_dists(x, c, dtype=jnp.float32):
    """(rows, K) squared distances, float32 out; `dtype` is what the inputs
    are rounded to before the one matmul (bfloat16: the control)."""
    xs, cs = x.astype(dtype), c.astype(dtype)
    prec = HIGHEST if dtype == jnp.float32 else None
    x2 = jnp.sum(jnp.square(xs.astype(jnp.float32)), -1, keepdims=True)
    c2 = jnp.sum(jnp.square(cs.astype(jnp.float32)), -1)[None, :]
    dot = jnp.matmul(xs, cs.T, precision=prec,
                     preferred_element_type=jnp.float32)
    return jnp.maximum(x2 + c2 - 2.0 * dot, 0.0)


def _blocks(n: int, block: int) -> int:
    if n % block:
        raise ValueError(f"{n} rows do not split into blocks of {block}")
    return n // block


# --------------------------------------------------------------- training

@functools.partial(jax.jit, static_argnames=("pb",))
def job_structure(x, centers, count, assign, send, *, pb):
    """What the answer says about its own pool, checked exactly.

    Returns (bad, creator, avail, k_start):
      bad     number of broken promises: an assignment outside [0, count),
              a slot with no point, creators out of order, a center that is
              not its creator point bit for bit, a creator that was not sent;
      creator (N,) point i is the first point of its slot: it made it;
      avail   (N,) centers point i was decided against: those of its
              epoch's start pool when not sent, those created before i when
              sent (a prefix of the pool: creators come in index order);
      k_start (N,) size of the pool at the start of point i's epoch.
    """
    n = x.shape[0]
    k_max = centers.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    in_range = (assign >= 0) & (assign < count)
    slot = jnp.where(in_range, assign, k_max)
    first = jax.ops.segment_min(jnp.where(in_range, idx, n), slot,
                                num_segments=k_max + 1)[:k_max]
    live = jnp.arange(k_max) < count
    has_point = live & (first < n)
    creator = jnp.where(has_point, first, n)              # n: none
    order_ok = jnp.where(live[1:], creator[1:] > creator[:-1], True)
    safe = jnp.minimum(creator, n - 1)
    same = jnp.all(centers == x[safe], axis=-1)
    bad = (jnp.sum(~in_range) + jnp.sum(live & ~has_point)
           + jnp.sum(~order_ok)
           + jnp.sum(has_point & ~same)
           + jnp.sum(has_point & ~send[safe]))
    creators_sorted = jnp.sort(creator)                   # n's go last
    k_start = jnp.searchsorted(creators_sorted, (idx // pb) * pb,
                               side="left").astype(jnp.int32)
    before = jnp.searchsorted(creators_sorted, idx,
                              side="left").astype(jnp.int32)
    avail = jnp.where(send, before, k_start)
    is_creator = in_range & (creator[jnp.clip(assign, 0, k_max - 1)] == idx)
    return bad.astype(jnp.int32), is_creator, avail, k_start


@functools.partial(jax.jit, static_argnames=("block", "dtype"))
def job_margins(x, centers, assign, avail, k_start, *, block: int = 1024,
                dtype=jnp.float32):
    """Per-point distances the rule is judged by, in blocks of rows.

    Returns (m_avail, m_start, d_assigned), each (N,): the least squared
    distance to the centers point i was decided against, to its epoch's
    start pool, and to the center it was assigned."""
    n, d = x.shape
    k_max = centers.shape[0]
    cols = jnp.arange(k_max, dtype=jnp.int32)[None, :]

    def one(args):
        xb, ab, avb, ksb = args
        d2 = sq_dists(xb, centers, dtype)
        m_av = jnp.min(jnp.where(cols < avb[:, None], d2, jnp.inf), -1)
        m_st = jnp.min(jnp.where(cols < ksb[:, None], d2, jnp.inf), -1)
        d_as = jnp.take_along_axis(
            d2, jnp.clip(ab, 0, k_max - 1)[:, None], -1)[:, 0]
        return m_av, m_st, d_as

    nb = _blocks(n, block)
    r = lambda a: a.reshape((nb, block) + a.shape[1:])
    out = jax.lax.map(one, (r(x), r(assign), r(avail), r(k_start)))
    return tuple(o.reshape(n) for o in out)


@jax.jit
def job_readings(assign, send, creator, avail, m_avail, m_start, d_assigned,
                 lam2):
    """(assign_gap, rule_gap) from the margins.

    assign_gap: the widest amount by which a point's assigned center lies
      farther than the nearest center it was decided against (creators are
      their own center and are left out; an assignment to a center the
      point could not have seen reads inf).
    rule_gap: the widest violation of the send / accept rule, in squared
      distance: a point kept although farther than λ from its start pool, a
      point sent although within λ of it, a creator within λ of an earlier
      center, a rejected point farther than λ from all it saw.  Negative
      when every decision was on the right side."""
    seen = (assign >= 0) & (assign < avail)
    gap = jnp.where(creator, -jnp.inf,
                    jnp.where(seen, d_assigned - m_avail, jnp.inf))
    v_keep = jnp.where(~send, m_start - lam2, -jnp.inf)
    v_send = jnp.where(send, lam2 - m_start, -jnp.inf)
    v_new = jnp.where(creator, lam2 - m_avail, -jnp.inf)
    v_rej = jnp.where(send & ~creator, m_avail - lam2, -jnp.inf)
    rule = jnp.maximum(jnp.maximum(v_keep, v_send), jnp.maximum(v_new, v_rej))
    return jnp.max(gap), jnp.max(rule)


def check_job(x, centers, count, assign, send, *, pb: int, lam: float,
              block: int = 1024) -> dict:
    """The numbers `correct` compares for one clustering job."""
    lam2 = jnp.float32(lam) ** 2
    bad, creator, avail, k_start = job_structure(x, centers, count, assign,
                                                 send, pb=pb)
    m_av, m_st, d_as = job_margins(x, centers, assign, avail, k_start,
                                   block=block)
    gap, rule = job_readings(assign, send, creator, avail, m_av, m_st, d_as,
                             lam2)
    return {"answers_bad": int(bad), "assign_gap": float(gap),
            "rule_gap": float(rule)}


@functools.partial(jax.jit, static_argnames=("block", "dtype"))
def control_answers(x, centers, avail, k_start, lam2, *,
                    block: int = 1024, dtype=jnp.bfloat16):
    """The control: each point's assignment and send decision recomputed in
    `dtype` against the same centers it was decided against (creators keep
    their own slot).  Judged by `check_job`, it has to fail."""
    n = x.shape[0]
    k_max = centers.shape[0]
    cols = jnp.arange(k_max, dtype=jnp.int32)[None, :]

    def one(args):
        xb, avb, ksb = args
        d2 = sq_dists(xb, centers, dtype)
        a = jnp.argmin(jnp.where(cols < avb[:, None], d2, jnp.inf), -1)
        m_st = jnp.min(jnp.where(cols < ksb[:, None], d2, jnp.inf), -1)
        return a.astype(jnp.int32), m_st > lam2

    nb = _blocks(n, block)
    r = lambda a: a.reshape((nb, block) + a.shape[1:])
    a, s = jax.lax.map(one, (r(x), r(avail), r(k_start)))
    return a.reshape(n), s.reshape(n)


def check_job_control(x, centers, count, assign, send, *, pb: int,
                      lam: float, block: int = 1024,
                      dtype=jnp.bfloat16) -> dict:
    """`check_job` of the control's answers: the program's pool, with every
    non-creator point's assignment and every send decision made in
    `dtype`."""
    lam2 = jnp.float32(lam) ** 2
    _, creator, avail, k_start = job_structure(x, centers, count, assign,
                                               send, pb=pb)
    a_c, s_c = control_answers(x, centers, avail, k_start, lam2,
                               block=block, dtype=dtype)
    a_c = jnp.where(creator, assign, a_c)
    s_c = jnp.where(creator, True, s_c)
    return check_job(x, centers, count, a_c, s_c, pb=pb, lam=lam,
                     block=block)


# ---------------------------------------------------------------- serving

@functools.partial(jax.jit, static_argnames=("k", "block", "dtype"))
def topk_ref(q, centers, count, *, k: int, block: int = 1024,
             dtype=jnp.float32):
    """(d2 (n, k) ascending, ids (n, k)) over the first `count` centers."""
    n = q.shape[0]
    cols = jnp.arange(centers.shape[0])[None, :]

    def one(qb):
        d2 = jnp.where(cols < count, sq_dists(qb, centers, dtype), jnp.inf)
        neg, ids = jax.lax.top_k(-d2, k)
        return -neg, ids.astype(jnp.int32)

    nb = _blocks(n, block)
    d, i = jax.lax.map(one, q.reshape(nb, block, q.shape[1]))
    return d.reshape(n, k), i.reshape(n, k)


@functools.partial(jax.jit, static_argnames=("block",))
def answer_gaps(q, centers, count, ids, scores, *, block: int = 1024):
    """Judge served answers (n, k): ids must be distinct live centers; the
    r-th id must lie no farther than the true r-th nearest (rank_gap), and
    each score must be that id's squared distance (score_err).  Returns
    (bad, rank_gap, score_err); padded rows (all ids -2) are skipped."""
    n, k = ids.shape
    k_max = centers.shape[0]
    cols = jnp.arange(k_max)[None, :]

    def one(args):
        qb, ib, sb = args
        d2 = jnp.where(cols < count, sq_dists(qb, centers), jnp.inf)
        neg, _ = jax.lax.top_k(-d2, k)
        best = -neg
        pad = jnp.all(ib == -2, axis=-1)
        ok_id = (ib >= 0) & (ib < count)
        got = jnp.take_along_axis(d2, jnp.clip(ib, 0, k_max - 1), -1)
        srt = jnp.sort(ib, axis=-1)
        dup = jnp.any(srt[:, 1:] == srt[:, :-1], axis=-1)
        bad = jnp.where(pad, 0, jnp.sum(~ok_id, -1) + dup)
        gap = jnp.where(pad[:, None] | ~ok_id, -jnp.inf, got - best)
        err = jnp.where(pad[:, None] | ~ok_id, -jnp.inf, jnp.abs(sb - got))
        return jnp.sum(bad), jnp.max(gap), jnp.max(err)

    nb = _blocks(n, block)
    r = lambda a: a.reshape((nb, block) + a.shape[1:])
    b, g, e = jax.lax.map(one, (r(q), r(ids), r(scores)))
    return jnp.sum(b), jnp.max(g), jnp.max(e)
