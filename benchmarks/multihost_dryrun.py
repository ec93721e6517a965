"""Two-process multi-host dry run: validates the distributed scan end-to-end
across REAL process boundaries (the DCN analog) — jax.distributed with a
local coordinator, 2 processes x 4 virtual CPU devices = one 8-device global
mesh, cross-process psum/pmin/pmax through the sharded downsample step.

Usage: python benchmarks/multihost_dryrun.py
(self-orchestrating: spawns its two worker processes and checks the result)
"""

from __future__ import annotations

import os
import subprocess
import sys

COORD = "localhost:12355"
NUM_PROCS = 2
LOCAL_DEVICES = 4
NUM_SERIES, NUM_BUCKETS, BUCKET_MS = 8, 8, 1000
ROWS = 4096  # global rows, split evenly across processes


def worker(pid: int) -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=COORD, num_processes=NUM_PROCS, process_id=pid
    )
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from horaedb_tpu.parallel import make_mesh
    from horaedb_tpu.parallel.scan import build_sharded_downsample

    assert jax.process_count() == NUM_PROCS
    assert jax.device_count() == NUM_PROCS * LOCAL_DEVICES
    mesh = make_mesh(series_parallel=2)  # rows=4 x series=2, spanning hosts

    # identical global dataset in both processes (deterministic), each
    # materializes only its row shard
    rng = np.random.default_rng(0)
    ts = rng.integers(0, NUM_BUCKETS * BUCKET_MS, ROWS).astype(np.int64)
    sid = rng.integers(0, NUM_SERIES, ROWS).astype(np.int32)
    vals = rng.normal(size=ROWS)
    valid = np.ones(ROWS, dtype=bool)

    sharding = NamedSharding(mesh, P("rows"))

    def put(arr):
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx]
        )

    d = [put(x) for x in (ts, sid, vals, valid)]
    fn = build_sharded_downsample(mesh, NUM_SERIES, NUM_BUCKETS, None, True)
    import jax.numpy as jnp

    out = fn(*d, (), jnp.asarray(0, jnp.int64), jnp.asarray(BUCKET_MS, jnp.int64))
    # outputs are sharded over "series" across processes: reduce to
    # replicated scalars under jit (global arrays are jit-only)
    probe = jax.jit(lambda o: (o["sum"].sum(), o["count"].sum()))
    t_sum, t_cnt = probe(out)
    total = float(jax.device_get(t_sum))
    count = float(jax.device_get(t_cnt))
    expect = float(vals.sum())
    ok = abs(total - expect) < 1e-6 * max(1.0, abs(expect)) and count == ROWS
    print(f"proc {pid}: sum={total:.4f} expect={expect:.4f} count={count} ok={ok}", flush=True)
    assert ok
    jax.distributed.shutdown()


def main() -> None:
    procs = []
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + f" --xla_force_host_platform_device_count={LOCAL_DEVICES}"
    ).strip()
    env.pop("PYTHONPATH", None)  # workers import only this checkout
    for pid in range(NUM_PROCS):
        procs.append(
            subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--worker", str(pid)],
                env=env,
            )
        )
    rc = [p.wait(timeout=300) for p in procs]
    if any(rc):
        raise SystemExit(f"multihost dryrun FAILED: exit codes {rc}")
    print("multihost dryrun OK: 2 processes x 4 devices, cross-process collectives")


if __name__ == "__main__":
    if "--worker" in sys.argv:
        worker(int(sys.argv[sys.argv.index("--worker") + 1]))
    else:
        main()
