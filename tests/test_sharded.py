"""Multi-device tests (subprocess with host-device emulation — conftest
deliberately leaves the main process at 1 device)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, devices: int = 8, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_occ_dpmeans_distributed_equals_local():
    """The mesh-sharded OCC run produces the same clustering as the
    single-device run — SPMD re-execution of the validator is exact."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import occ_dp_means
from repro.data import dp_stick_breaking_data
x, _, _ = dp_stick_breaking_data(512, seed=1)
x = jnp.asarray(x)
from jax.sharding import AxisType
mesh = jax.make_mesh((8,), ("data",),
                     axis_types=(AxisType.Auto,))
r_local = occ_dp_means(x, 4.0, pb=64, k_max=128, max_iters=2)
r_dist = occ_dp_means(x, 4.0, pb=64, k_max=128, max_iters=2, mesh=mesh)
assert int(r_local.pool.count) == int(r_dist.pool.count)
assert np.array_equal(np.asarray(r_local.z), np.asarray(r_dist.z))
np.testing.assert_allclose(np.asarray(r_local.pool.centers),
                           np.asarray(r_dist.pool.centers), atol=1e-5)
print("DIST_OK", int(r_dist.pool.count))
""")
    assert "DIST_OK" in out


def test_cp_decode_equals_tp_decode():
    """Context-parallel (seq-sharded cache, psum-combined softmax) decode
    matches head-TP decode numerically."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import ARCHS, reduced
from repro.distributed.shardings import shard_ctx
from repro.models import build_model
cfg = reduced(ARCHS["granite-3-2b"]).replace(dtype="float32")
m = build_model(cfg)
from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
rng = np.random.default_rng(0)
B, CL = 4, 32
with shard_ctx(mesh), mesh:
    params = m.init(jax.random.key(0))
    caches = m.init_cache(B, CL)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, 1)), jnp.int32)
    pos = jnp.asarray(rng.integers(4, 8, (B,)), jnp.int32)
    lg_tp, c_tp = m.decode_step(params, caches, toks, pos, decode_mode="tp")
    lg_cp, c_cp = m.decode_step(params, caches, toks, pos, decode_mode="cp")
np.testing.assert_allclose(np.asarray(lg_tp), np.asarray(lg_cp), atol=2e-3)
for a, b in zip(jax.tree.leaves(c_tp), jax.tree.leaves(c_cp)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
print("CP_OK")
""")
    assert "CP_OK" in out


def test_sharded_train_step_matches_single_device():
    """pjit'd train step on a (2,2,2) mesh == single-device step."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import ARCHS, TrainConfig, reduced
from repro.distributed.shardings import shard_ctx
from repro.models import build_model
from repro.training.step import make_train_step, train_state_init
from repro.data.tokens import TokenPipeline
cfg = reduced(ARCHS["qwen3-4b"]).replace(dtype="float32")
m = build_model(cfg)
tcfg = TrainConfig()
pipe = TokenPipeline(cfg.vocab, 8, 16, seed=0)
batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}

state0 = train_state_init(m.init(jax.random.key(0)), tcfg)
s_ref, met_ref = make_train_step(m, tcfg)(state0, batch)

from jax.sharding import AxisType
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,) * 3)
with shard_ctx(mesh), mesh:
    state1 = train_state_init(m.init(jax.random.key(0)), tcfg)
    s_sh, met_sh = jax.jit(make_train_step(m, tcfg))(state1, batch)
assert abs(float(met_ref["loss"]) - float(met_sh["loss"])) < 1e-4
for a, b in zip(jax.tree.leaves(s_ref.params), jax.tree.leaves(s_sh.params)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)
print("TRAIN_SHARD_OK", float(met_sh["loss"]))
""")
    assert "TRAIN_SHARD_OK" in out


def test_compressed_psum_shard_map():
    """int8 error-feedback psum over a real mesh axis: exact integer
    reduction, residual bounded."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.optim.compression import compressed_psum_with_feedback, ef_init
from jax.sharding import AxisType
mesh = jax.make_mesh((4,), ("pod",),
                     axis_types=(AxisType.Auto,))
rng = np.random.default_rng(0)
g_all = jnp.asarray(rng.normal(size=(4, 64)).astype(np.float32))
def body(g):
    grads = {"w": g[0]}
    ef = ef_init(grads)
    out, ef2 = compressed_psum_with_feedback(grads, ef, "pod")
    return out["w"], ef2.residual["w"]
summed, resid = jax.shard_map(body, mesh=mesh, in_specs=P("pod"),
                              out_specs=(P(), P("pod")))(g_all)
true = np.asarray(g_all).sum(0)
err = np.abs(np.asarray(summed) - true).max()
amax = np.abs(np.asarray(g_all)).max()
assert err <= 4 * (amax / 127) + 1e-6, err
print("PSUM_OK", err)
""", devices=4)
    assert "PSUM_OK" in out


def test_elastic_remesh_restore(tmp_path):
    """Checkpoint on a (4,2) mesh, 'lose' devices, restore onto (2,2)."""
    out = _run(f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import CheckpointManager
from repro.distributed.elastic import plan_shrunk_mesh, build_mesh_from_plan
from jax.sharding import AxisType
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
w = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)
sharded = jax.device_put(w, NamedSharding(mesh, P("data", "model")))
mgr = CheckpointManager({str(tmp_path)!r})
mgr.save(3, {{"w": sharded}})
plan = plan_shrunk_mesh(mesh, n_failed=3)   # 2 per rank -> lose 2 ranks
assert plan.new_shape["data"] == 2
new_mesh = build_mesh_from_plan(plan)
new_sh = {{"w": NamedSharding(new_mesh, P("data", "model"))}}
step, restored = mgr.restore({{"w": jax.eval_shape(lambda: w)}}, shardings=new_sh)
np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(w))
assert restored["w"].sharding.mesh.shape["data"] == 2
print("ELASTIC_OK")
""", devices=8)
    assert "ELASTIC_OK" in out
