"""One small run of the four-chip training cell on four virtual CPU
devices, in a process of its own (`test_correct.py` starts it with
XLA_FLAGS=--xla_force_host_platform_device_count=4).

    python chipbench/tests/mesh_case.py [no_exchange | readings SEED]

Prints the result line.  `no_exchange` leaves out the exchange between the
chips: each chip's proposals stay on it, and the replicated validator sees
only the first chip's.  `readings SEED` prints instead what
`test_same_readings.py` compares, of a run on that seed."""
from __future__ import annotations

import json
import sys

import test_correct as tc


def no_exchange():
    import jax
    from repro.distributed import shardings
    orig = shardings.occ_propose_shard_map

    def shard_map(propose, mesh, data_axis, pb):
        def local(pool, x_e, state_e):
            send, payload, aux, safe = propose(pool, x_e, state_e)
            mine = jax.lax.axis_index(data_axis) == 0
            return send & mine, payload, aux, safe
        return orig(local, mesh, data_axis, pb)

    shardings.occ_propose_shard_map = shard_map


if __name__ == "__main__":
    if sys.argv[1:2] == ["readings"]:
        import test_same_readings
        print(json.dumps(test_same_readings.readings(
            "laion512.train.4chip", int(sys.argv[2]))))
        sys.exit(0)
    if sys.argv[1:] == ["no_exchange"]:
        no_exchange()
    print(json.dumps(tc.run_small("laion512.train.4chip")))
