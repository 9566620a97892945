# Test harness: force an 8-device virtual CPU platform BEFORE jax imports,
# mirroring the reference's "mock MPI" seam that lets distributed code run
# in one process (ref:mpisppy/MPI.py:27-90 and the no-mpi4py CI job,
# ref:.github/workflows/test_pr_and_main.yml:27-48).  Every sharded code
# path is exercised on this virtual mesh; real-TPU behavior only differs
# in performance.
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Pin the platform via the config API too, so a process that has a TPU
# runtime installed still runs the suite on the CPU.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
