"""Rehearsal, without the chip: compile the Pallas PDHG window for a
described four-chip v5e (2x2) with S = 400,000 scenarios split over the
scenario axis, the size at which a full sslp_15_45 wheel no longer fits
one chip.

    JAX_PLATFORMS=cpu python3 perfbench/rehearse_sharded_window.py

Today it fails: Mosaic kernels cannot be partitioned automatically
("Please wrap the call in a shard_map"), so the four-chip cell
`sslp_15_45.s400k.4chip` waits for the change that wraps
ops/pdhg_pallas.run_window in shard_map.  That change runs this script to
show the window compiles, prints the per-chip memory, and then adds the
cell.  Exit code 0 when it compiles, 1 when it does not.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

S, N, M = 400_000, 705, 60


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as Ps

    from mpisppy_tpu.ops import pdhg_pallas
    from mpisppy_tpu.ops.boxqp import BoxQP

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices), ("s",))
    lanes, shared = NamedSharding(mesh, Ps("s")), NamedSharding(mesh, Ps())

    def sd(shape, dt=jnp.float32, sh=lanes):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    def window(c, q, A, bl, bu, lo, hi, x, y, xs, ys, tau, sigma, done):
        p = BoxQP(c=c, q=q, A=A, bl=bl, bu=bu, l=lo, u=hi)
        return pdhg_pallas.run_window(p, x, y, xs, ys, tau, sigma, done, 40,
                                      precision="bf16x3")

    args = (sd((S, N)), sd((S, N)), sd((M, N), sh=shared), sd((S, M)),
            sd((S, M)), sd((N,), sh=shared), sd((N,), sh=shared),
            sd((S, N)), sd((S, M)), sd((S, N)), sd((S, M)), sd((S,)),
            sd((S,)), sd((S,), jnp.bool_))
    try:
        compiled = jax.jit(window).lower(*args).compile()
    except Exception as e:  # noqa: BLE001 - the rehearsal reports it
        print(f"does not compile: {type(e).__name__}: {e}".splitlines()[0])
        return 1
    print("compiles; per-chip memory:", compiled.memory_analysis())
    return 0


if __name__ == "__main__":
    sys.exit(main())
