"""What every cell shares: the checkout's paths, the device check, the peaks
table, the compile clock, the data generator and the result line.

Nothing here imports the program under test (`repro`): the data and the
yardstick belong to the benchmark, so a change to the program cannot move
them.
"""
from __future__ import annotations

import glob
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# The one fixed place JAX's persistent compilation cache lives in: inside
# the checkout, so that the parent and the change of a comparison never
# share it, and at a path that does not move, since the path is part of
# the cache's key.  `repro.launch.compile_cache` names the same directory.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def benchmark_spec() -> dict:
    """BENCHMARK.json, with the entries of each `candidates/<cell>.json`
    appended: a candidate is a cell that runs and proves correct but is not
    admitted yet (its spread does not fit a bound), so it is measured by
    hand and tested, never by the benchmark's own runs.  Admitting it is
    moving its entries into BENCHMARK.json."""
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for path in sorted(glob.glob(os.path.join(HERE, "candidates", "*.json"))):
        for key, entries in load_json(path).items():
            spec[key] = spec[key] + entries
    return spec


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload named {name!r} in BENCHMARK.json or "
                     "chipbench/candidates")


def config_of(spec: dict, cell: dict) -> dict:
    for c in spec["configs"]:
        if c["name"] == cell["config"]:
            return load_json(os.path.join(ROOT, c["file"]))
    raise SystemExit(f"no config named {cell['config']!r}")


def mix_of(cell: dict) -> dict:
    return load_json(os.path.join(HERE, "mixes", cell["traffic"] + ".json"))


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at CACHE_DIR and cache every
    program, however quickly it compiled, so that a second run of a cell
    compiles nothing.  Call before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    return CACHE_DIR


def require_devices(chips: int):
    """The cell's chips, or NoChip.  Never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found platform {devs[0].platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def peaks_for(device_kind: str) -> dict:
    """The peak table's row for this device; an unknown device is an error."""
    table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "chipbench/peaks.json")
    return table[device_kind]


class CompileClock:
    """Counts XLA backend compilations and their seconds (a persistent-cache
    hit is not a compilation).  `mark()` starts counting a window."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        self.loads = 0
        self._mark = (0.0, 0, 0)

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.count += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.loads += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self) -> None:
        self._mark = (self.seconds, self.count, self.loads)

    def loads_since_mark(self) -> int:
        """Programs loaded from the persistent cache since `mark()`."""
        return self.loads - self._mark[2]

    def since_mark(self) -> tuple[float, int]:
        return self.seconds - self._mark[0], self.count - self._mark[1]


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63: the seed is split into
    two 32-bit words, so large seeds neither overflow nor collide."""
    import jax
    import jax.numpy as jnp
    s = int(seed) % (1 << 64)
    hi, lo = s >> 32, s & 0xFFFFFFFF
    k = jax.random.key(0)
    k = jax.random.fold_in(k, jnp.uint32(hi))
    return jax.random.fold_in(k, jnp.uint32(lo))


def unit_means(key, n_comp: int, d: int):
    """n_comp unit-norm mixture means, drawn on the device."""
    import jax
    import jax.numpy as jnp
    m = jax.random.normal(key, (n_comp, d), jnp.float32)
    return m / jnp.linalg.norm(m, axis=-1, keepdims=True)


def mixture_points(key, means, z, noise: float):
    """Unit-norm points around means[z] with isotropic noise of total
    variance `noise` before normalizing (the generator of chip_smoke.py)."""
    import jax
    import jax.numpy as jnp
    d = means.shape[-1]
    eps = jax.random.normal(key, (z.shape[0], d), jnp.float32)
    p = means[z] + jnp.sqrt(noise / d) * eps
    return p / jnp.linalg.norm(p, axis=-1, keepdims=True)


def mixture(seed: int, n: int, n_comp: int, d: int, noise: float):
    """(means, points): n points drawn uniformly over n_comp components, in
    one jitted call on the device."""
    import jax

    @jax.jit
    def draw(key):
        km, kz, ke = jax.random.split(key, 3)
        means = unit_means(km, n_comp, d)
        z = jax.random.randint(kz, (n,), 0, n_comp)
        return means, mixture_points(ke, means, z, noise)

    return draw(seed_key(seed))


def rotated(seed: int, x):
    """x times an orthogonal matrix drawn from the seed (QR of a Gaussian
    matrix), at HIGHEST precision, on the device: the same geometry in new
    coordinates, so every seed poses the same problem in other numbers."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def turn(key, x):
        d = x.shape[-1]
        q, r = jnp.linalg.qr(jax.random.normal(key, (d, d), jnp.float32))
        q = q * jnp.sign(jnp.diagonal(r))[None, :]
        return jnp.matmul(x, q, precision=jax.lax.Precision.HIGHEST)

    return turn(jax.random.fold_in(seed_key(seed), 2), x)


def device_info(devs, peak_bytes: int) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak_bytes)}


def memory_peak(devs) -> int:
    """Peak bytes in use on the fullest of the cell's chips."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def quantile(values, q: float) -> float:
    """The q-quantile (0..1) by linear interpolation; inf counts as a value
    above every finite one, so failed requests miss every limit."""
    v = sorted(values)
    if not v:
        return math.nan
    pos = q * (len(v) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    if math.isinf(v[hi]) or math.isinf(v[lo]):
        return v[hi] if pos > lo else v[lo]
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def metric(value, unit: str) -> dict:
    return {"value": float(value), "unit": unit}


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def now() -> float:
    return time.perf_counter()


TRACE_ROOT = os.path.join(ROOT, ".bench_traces")


def trace_dir(cell: str) -> str:
    """A fresh directory for this cell's profile, inside the checkout."""
    import shutil
    path = os.path.join(TRACE_ROOT, cell)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path
