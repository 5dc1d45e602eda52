"""Paper Figure 4: strong scaling of the distributed algorithms.

The paper measured wall time on 1/2/4/8 EC2 instances.  This container has
one physical core, so emulated host devices cannot show real speedup;
what this benchmark validates is (a) the distributed code path end-to-end
on a P-way mesh, and (b) the *workload model* the paper's scaling rests on:
per-worker points N/P and master (validator) load <= Pb + K_N per epoch.
We report both wall time and the modeled speedup T(P) ~ N/P + master_load,
which reproduces Fig 4's shape (near-perfect for DP/BP, first-epoch-bound
for OFL).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

_WORKER = """
import time, jax, jax.numpy as jnp, numpy as np
from repro.core import occ_dp_means, occ_ofl, occ_bp_means
from repro.data import dp_stick_breaking_data, bp_stick_breaking_data
P = {P}
algo = "{algo}"
n, pb = {n}, {pb}
mesh = jax.make_mesh((P,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
if algo == "bpmeans":
    x, _, _ = bp_stick_breaking_data(n, seed=0)
else:
    x, _, _ = dp_stick_breaking_data(n, seed=0)
x = jnp.asarray(x)
def go():
    if algo == "dpmeans":
        return occ_dp_means(x, 4.0, pb=pb, k_max=512, max_iters=1, mesh=mesh)
    if algo == "ofl":
        return occ_ofl(x, 4.0, pb=pb, key=jax.random.key(0), k_max=1024, mesh=mesh)
    return occ_bp_means(x, 4.0, pb=pb, k_max=512, max_iters=1, mesh=mesh)
res = go()  # compile + run once
t0 = time.time(); res = go(); dt = time.time() - t0
sent = int(np.asarray(res.stats.proposed).sum())
acc = int(np.asarray(res.stats.accepted).sum())
print("RESULT", dt, sent, acc)
"""


def run(n: int = 16384, pb: int = 2048, ps=(1, 2, 4, 8), quiet: bool = False):
    rows = []
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for algo in ("dpmeans", "ofl", "bpmeans"):
        base_model = None
        for p in ps:
            env = dict(os.environ)
            # Each child emulates P hosts as P CPU devices; it must not
            # reach for a chip the parent process may already hold.
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={p}"
            env["PYTHONPATH"] = os.path.join(repo, "src")
            code = _WORKER.format(P=p, algo=algo, n=n, pb=pb)
            t0 = time.time()
            out = subprocess.run([sys.executable, "-c", code], env=env,
                                 capture_output=True, text=True, timeout=1200)
            assert out.returncode == 0, out.stderr[-2000:]
            line = [l for l in out.stdout.splitlines() if l.startswith("RESULT")][0]
            _, dt, sent, acc = line.split()
            dt, sent, acc = float(dt), int(sent), int(acc)
            # workload model: worker n/P per epoch + serial validation `sent`
            model = n / p + sent
            if base_model is None:
                base_model = model
            rows.append((f"fig4_{algo}_P{p}", dt * 1e6,
                         f"modeled_speedup={base_model / model:.2f};"
                         f"master_load={sent};accepted={acc}"))
            if not quiet:
                print(f"{rows[-1][0]},{dt * 1e6:.0f},{rows[-1][2]}")
    return rows


if __name__ == "__main__":
    run()
