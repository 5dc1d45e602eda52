"""The OCC path's Pallas kernels compiled for a described TPU v5e.

No chip is needed: the TPU compiler installed with jax compiles for a
`v5e:2x2` topology that is described, not attached, and refuses what the
chip would refuse (block shapes off the (8, 128) tiling, layouts Mosaic
cannot verify, kernels GSPMD would have to partition).  Interpret mode
catches none of that.  Each case asserts the kernel is in the compiled
program (`tpu_custom_call`).

The topology is described inside a module fixture, never at import time:
only one process may hold the TPU library at once, and under pytest-xdist
every worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro.kernels.dpmeans_assign import dpmeans_assign
from repro.kernels.flash_attention import flash_attention
from repro.kernels.topk_stream import topk_multiprobe_stream, topk_stream
from repro.serving import ServeConfig
from repro.serving.snapshot import build_hier, next_bucket

D = 96                       # DEEP width (big-ann-benchmarks)
PB = 256                     # the engine's propose width in chip_smoke.py
K_POOL = 32768               # chip_smoke.py's center capacity
K_INDEX = 131072             # a million-center-class flat index
K_OFL = 1 << 20              # deep96-ofl's facility slots
SERVE_BUCKETS = [1 << i for i in range(3, 13)]   # 8 .. ServeConfig max_bucket


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def chip(topo):
    """Shape maker for arguments placed on one described chip."""
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one)


def _kernel_compiled(fn, *args, **kwargs) -> str:
    text = jax.jit(fn, **kwargs).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_serve_buckets_cover_service_config():
    cfg = ServeConfig()
    assert SERVE_BUCKETS == [next_bucket(n, cfg.min_bucket, cfg.max_bucket)
                             for n in SERVE_BUCKETS]
    assert SERVE_BUCKETS[0] == cfg.min_bucket
    assert SERVE_BUCKETS[-1] == cfg.max_bucket


@pytest.mark.parametrize("rows", [PB] + SERVE_BUCKETS)
def test_dpmeans_assign_compiles(chip, rows):
    """The propose width and every assign bucket the service can dispatch,
    against the full center capacity with a traced active count."""
    _kernel_compiled(
        lambda x, c, m, n: dpmeans_assign(x, c, m, count=n),
        chip((rows, D)), chip((K_POOL, D)), chip((K_POOL,), jnp.bool_),
        chip((), jnp.int32))


@pytest.mark.parametrize("rows,d", [
    (1024, 512),     # laion512.train: the epoch in one row block
    (256, D),        # deep96.train
    (256, 512),      # laion512.train.4chip: one chip's quarter
])
def test_dpmeans_assign_compiles_at_cell_shapes(chip, rows, d):
    """The benchmark cells' propose shapes at their real K_max, with the
    default tiles: a tile past the VMEM budget fails here, not on the
    chip."""
    k = 65536
    _kernel_compiled(
        lambda x, c, m, n: dpmeans_assign(x, c, m, count=n),
        chip((rows, d)), chip((k, d)), chip((k,), jnp.bool_),
        chip((), jnp.int32))


def test_dpmeans_assign_compiles_at_ofl_pool(chip):
    """deep96-ofl.train's propose: one 256-row epoch against the 2^20-slot
    facility pool OFL needs for its ~477k facilities."""
    _kernel_compiled(
        lambda x, c, m, n: dpmeans_assign(x, c, m, count=n),
        chip((PB, D)), chip((K_OFL, D)), chip((K_OFL,), jnp.bool_),
        chip((), jnp.int32))


def _custom_calls(text: str) -> set:
    """Names of the Pallas calls in a compiled program, numbering dropped."""
    return {re.sub(r"\.\d+$", "", n) for n in re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text)}


@pytest.mark.parametrize("rows,d,k", [
    (1024, 512, 65536),   # laion512.train
    (256, 512, 65536),    # laion512.train.4chip: one chip's quarter
    (256, D, 65536),      # deep96.train
    (PB, D, K_OFL),       # deep96-ofl.train
])
def test_screened_assign_compiles_at_cell_shapes(chip, rows, d, k):
    """The propose through the screen at the cells' shapes: the screen,
    the re-score and, under a `cond`, the exact kernel, with the screen's
    extra scratch inside the scoped VMEM it asks for."""
    from repro.kernels.dpmeans_assign import screened_assign, takes_screen
    assert takes_screen(rows, d, k)
    text = _kernel_compiled(
        lambda x, c, m, n: screened_assign(x, c, m, count=n),
        chip((rows, d)), chip((k, d)), chip((k,), jnp.bool_),
        chip((), jnp.int32))
    assert _custom_calls(text) == {"dpmeans_assign_screen", "dpmeans_assign"}
    assert re.search(r" conditional\(", text)


@pytest.mark.parametrize("rows", SERVE_BUCKETS)
def test_serve_assign_takes_the_screen_from_r_rows(chip, monkeypatch, rows):
    """Every assign bucket the service dispatches: those below R rows
    compile to the exact kernel alone, the rest to the screen with the
    exact kernel as its fallback."""
    from repro.kernels import ops
    from repro.kernels.dpmeans_assign import SCREEN_MIN_ROWS
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    text = _kernel_compiled(
        lambda x, c, m, n, v: ops.serve_assign(x, c, m, count=n, n_valid=v),
        chip((rows, D)), chip((K_POOL, D)), chip((K_POOL,), jnp.bool_),
        chip((), jnp.int32), chip((), jnp.int32))
    want = {"dpmeans_assign"}
    if rows >= SCREEN_MIN_ROWS:
        want.add("dpmeans_assign_screen")
    assert _custom_calls(text) == want


@pytest.mark.parametrize("rows", SERVE_BUCKETS)
def test_topk_stream_compiles(chip, rows):
    _kernel_compiled(
        lambda x, c, m, n: topk_stream(x, c, m, 10, count=n),
        chip((rows, D)), chip((K_INDEX, D)), chip((K_INDEX,), jnp.bool_),
        chip((), jnp.int32))


@pytest.fixture(scope="module")
def hier_shapes():
    """(n_cells, shard_cap) that build_hier makes for a K_INDEX index."""
    rng = np.random.default_rng(0)
    c = rng.normal(size=(K_INDEX, D)).astype(np.float32)
    c /= np.linalg.norm(c, axis=1, keepdims=True)
    h = build_hier(jnp.asarray(c), jnp.ones((K_INDEX,), bool), K_INDEX)
    return h.n_cells, h.shard_cap


@pytest.mark.parametrize("rows", [8, 64, 4096])
def test_topk_multiprobe_stream_compiles(chip, hier_shapes, rows):
    """Union width as the service sizes it for 8 probes per query."""
    n_cells, s = hier_shapes
    u = min(n_cells, next_bucket(rows * 8, 1))
    _kernel_compiled(
        lambda x, f, fi, fm, cells, mem, n: topk_multiprobe_stream(
            x, f, fi, fm, cells, mem, 10, u_count=n),
        chip((rows, D)), chip((n_cells, s, D)), chip((n_cells, s), jnp.int32),
        chip((n_cells, s), jnp.bool_), chip((u,), jnp.int32),
        chip((rows, u), jnp.bool_), chip((), jnp.int32))


def test_flash_attention_compiles(chip):
    _kernel_compiled(
        flash_attention, chip((1, 32, 2048, 128), jnp.bfloat16),
        chip((1, 8, 2048, 128), jnp.bfloat16),
        chip((1, 8, 2048, 128), jnp.bfloat16))


@pytest.mark.parametrize("chips,d,pb,k_max", [
    pytest.param(1, D, PB, K_POOL, id="1"),
    pytest.param(4, D, PB, K_POOL, id="4"),
    pytest.param(4, 512, 1024, 65536, id="4-laion512"),
])
def test_occ_engine_pass_compiles(topo, monkeypatch, chips, d, pb, k_max):
    """A whole DP-means pass program on the Pallas path — on one chip, and
    sharded over the 2x2 host's data axis with the propose under
    shard_map (GSPMD cannot partition the kernel itself).  Each chip's
    rows take the screen from R rows on: laion512's 256 a chip do,
    chip_smoke.py's 64 do not."""
    from repro.core import DPMeansTransaction
    from repro.core.engine import _engine_pass_jit
    from repro.core.occ import make_pool
    from repro.kernels import ops
    from repro.kernels.dpmeans_assign import SCREEN_MIN_ROWS
    # Code that asks for the backend sees this CPU; steer it to the kernel.
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    if chips == 1:
        mesh = None
        place = SingleDeviceSharding(topo.devices[0])
    else:
        mesh = Mesh(np.array(topo.devices).reshape(4), ("data",),
                    axis_types=(AxisType.Auto,))
        place = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    txn = DPMeansTransaction(1.0, k_max=k_max)
    pool = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=place),
        jax.eval_shape(lambda: make_pool(k_max, d)))
    x = jax.ShapeDtypeStruct((1 << 18, d), jnp.float32, sharding=place)
    text = _engine_pass_jit.lower(
        txn, pool, x, (), pb=pb, cap_warm=None, cap_rest=None, n_warm=0,
        n_bootstrap=0, mesh=mesh, data_axis="data").compile().as_text()
    rows = pb // chips
    want = {"dpmeans_assign"}
    if rows >= SCREEN_MIN_ROWS:
        want.add("dpmeans_assign_screen")
    assert _custom_calls(text) == want
    if mesh is not None:
        # the kernel runs on each chip's quarter of the epoch
        assert f"f32[{rows},1]" in text


def test_ofl_pass_and_state_compile(chip, monkeypatch):
    """deep96-ofl.train's programs on one chip: a call's uniform state
    draw (`occ.state`) and the whole OCC OFL pass over a 2^17-point call,
    the uniforms as its per-point state, against a 2^20-slot pool.  Its
    width-256 epochs resolve by the log-depth fixed point, whose overflow
    `cond` carries no pool, and the program copies the pool no more than
    the two layout copies at the pass's boundary."""
    from repro.core import OFLTransaction
    from repro.core.engine import _engine_pass_jit
    from repro.core.ofl import _draw_uniforms
    from repro.core.occ import make_pool
    from repro.kernels import ops
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    n = 1 << 17
    shaped = lambda a: chip(a.shape, a.dtype)
    key = chip((), jax.random.key(0).dtype)
    state = _draw_uniforms.lower(key, chip((), jnp.int32), n=n).compile()
    assert "occ.state" in state.as_text()
    txn = OFLTransaction(chip((), jnp.float32), K_OFL, key)
    pool = jax.tree.map(shaped, jax.eval_shape(lambda: make_pool(K_OFL, D)))
    text = _engine_pass_jit.lower(
        txn, pool, chip((n, D)), chip((n,)), pb=PB, cap_warm=None,
        cap_rest=None, n_warm=0, n_bootstrap=0, mesh=None,
        data_axis="data").compile().as_text()
    assert "tpu_custom_call" in text
    assert "/occ.scan/cond" in text
    pool = rf"f32\[{K_OFL},{D}\]"
    assert not re.search(pool + r"[^\n]* conditional\(", text)
    assert len(re.findall(pool + r"\{[^}]*\} copy\(", text)) <= 2
