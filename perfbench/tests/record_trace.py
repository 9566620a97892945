"""Record the small chip trace that tests/test_trace.py reads.

    chiprun -- python3 perfbench/tests/record_trace.py chiprun_out/fixture

On the chip: one traced window (`perfbench/traced`) holding one hub-like
`wheel_sync` step in which the host sleeps 50 ms with the device idle,
then one Pallas window call at S = 32,768 (the smallest batch on which the
engine picks the kernel) and one XLA op.  The capture's .xplane.pb goes to
<dir>/chip_trace.xplane.pb (default perfbench/tests/fixtures) and its
facts to chip_trace.json beside it.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
S, N, M = 32768, 705, 60
SLEEP_S = 0.05


def main() -> int:
    import jax
    import jax.numpy as jnp

    from mpisppy_tpu.ops import pdhg_pallas
    from mpisppy_tpu.ops.boxqp import BoxQP

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    A = jax.random.normal(k[0], (M, N)) * 0.1
    c = jax.random.normal(k[1], (S, N))
    p = BoxQP(c=c, q=jnp.zeros_like(c), A=A, bl=jnp.zeros((S, M)),
              bu=jnp.ones((S, M)), l=jnp.zeros(N), u=jnp.ones(N))
    x, y = jnp.zeros((S, N)), jnp.zeros((S, M))
    tau = sigma = jnp.full((S,), 0.1)
    done = jnp.zeros((S,), bool)

    @jax.jit
    def window(p, x, y):
        return pdhg_pallas.run_window(p, x, y, x, y, tau, sigma, done, 40,
                                      precision="bf16x3")

    @jax.jit
    def xla_op(v):
        return jnp.tanh(v) * 2.0

    jax.block_until_ready((window(p, x, y), xla_op(c)))   # compile
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("perfbench/traced"):
        with jax.profiler.StepTraceAnnotation("wheel_sync", step_num=1):
            time.sleep(SLEEP_S)
        out = window(p, x, y)
        out = (out, xla_op(c))
        jax.block_until_ready(out)
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                     recursive=True)[0]
    fix = sys.argv[1] if len(sys.argv) > 1 else FIX
    os.makedirs(fix, exist_ok=True)
    shutil.copy(path, os.path.join(fix, "chip_trace.xplane.pb"))
    with open(os.path.join(fix, "chip_trace.json"), "w") as f:
        json.dump({"device_kind": jax.devices()[0].device_kind,
                   "scenarios": S, "columns": N, "rows": M,
                   "sleep_s": SLEEP_S, "host_wall_s": wall,
                   "bytes": os.path.getsize(path)}, f, indent=1)
    print(json.dumps({"ok": True, "bytes": os.path.getsize(path)}))
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
