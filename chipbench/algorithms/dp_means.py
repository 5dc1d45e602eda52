"""OCC DP-means (paper Alg. 3 with DPValidate): the program's
`DPMeansTransaction`, judged by `reference.check_job`.  A job's answer is,
per point, its assignment (N,) int32 and whether it was sent (N,) bool, and
the pool of centers.  The control recomputes every assignment and send
decision one precision step down (`reference.check_job_control`)."""
import flops
import reference

# The propose kernel as the device trace names it: a Pallas call's op is
# named after the jitted function that makes it.
PROPOSE_KERNEL = "dpmeans_assign"


def transaction(cfg, seed):
    """DP-means draws nothing: the seed does not enter."""
    from repro.core import DPMeansTransaction
    return DPMeansTransaction(cfg["lam"], k_max=cfg["k_max"])


def check(x, answer, cfg, mix):
    assign, send, pool = answer
    return reference.check_job(x, pool.centers, pool.count, assign, send,
                               pb=cfg["pb"], lam=cfg["lam"],
                               block=mix.get("check_block", 1024))


def control(x, answer, cfg, mix, dtype):
    assign, send, pool = answer
    return reference.check_job_control(x, pool.centers, pool.count, assign,
                                       send, pb=cfg["pb"], lam=cfg["lam"],
                                       block=mix.get("check_block", 1024),
                                       dtype=dtype)


def propose_work(k_start, accepted, pb, d):
    """Each epoch's pb points against the K_e centers live at its start."""
    f, b = flops.propose_epochs([k_start] + list(accepted), pb, d)
    return f[1:], b[1:]


def small(cfg):
    """The configuration at a size the CPU runs in seconds: its width,
    fewer components, points and slots, and λ = 1 with more noise, so that
    components hold several centers and some decisions fall near λ, as
    they do at full size."""
    return dict(cfg, n_components=256, n_points=16384, k_max=4096,
                noise=0.8, lam=1.0, pb=min(cfg["pb"], 256))


# ------------------------------------------- faults planted by the tests

def _stale_state(monkeypatch):
    """A pass that returns the pool it was given: no center is ever kept."""
    from repro.core.engine import OCCEngine
    orig = OCCEngine._commit_stream_pass

    def commit(self, xb, state):
        before = self._pool
        if before is None:
            before = self.txn.init_pool(xb[:self.pb])
        res = orig(self, xb, state)
        self._pool = before
        return res._replace(pool=before)

    monkeypatch.setattr(OCCEngine, "_commit_stream_pass", commit)


def _half_batch(monkeypatch):
    """Half of each call's points left out; their answers copied from the
    half that ran."""
    import jax.numpy as jnp
    from repro.core.engine import OCCEngine
    orig = OCCEngine.partial_fit

    def partial_fit(self, xb, **kw):
        res = orig(self, xb[:xb.shape[0] // 2], **kw)
        return res._replace(assign=jnp.concatenate([res.assign] * 2),
                            send=jnp.concatenate([res.send] * 2))

    monkeypatch.setattr(OCCEngine, "partial_fit", partial_fit)


def _altered_answer(monkeypatch):
    """One point's assignment changed where the pass produces it."""
    from repro.core.engine import OCCEngine
    orig = OCCEngine.partial_fit

    def partial_fit(self, xb, **kw):
        res = orig(self, xb, **kw)
        k = int(self.pool.count)
        a = res.assign.at[7].set((res.assign[7] + 1) % k)
        return res._replace(assign=a)

    monkeypatch.setattr(OCCEngine, "partial_fit", partial_fit)


FAULTS = (_stale_state, _half_batch, _altered_answer)
