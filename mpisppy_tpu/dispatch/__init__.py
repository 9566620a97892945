###############################################################################
# Dispatch subsystem: the one gate between host-driven solve loops and
# the device (docs/dispatch.md).
#
# The round-5 verdict's top item: sslp_15_45 re-certification runs never
# completed because per-step solve_mip calls from the Lagrangian-oracle
# loops (algos/mip.py) stalled the device runtime with thousands of tiny,
# variably-shaped dispatches.  The reference never faces this — each
# scenario subproblem is one opaque Gurobi call on its own rank
# (ref:mpisppy/spopt.py:884) — but a TPU-native wheel needs the
# inference-serving shape instead: coalesce many small requests into
# fixed-shape batched solves (MPAX, arXiv:2412.09734) and keep
# utilization high with a bounded pipeline of large dispatches (Large
# Scale Distributed Linear Algebra With TPUs, arXiv:2112.09017).
#
# Three pieces (one module each):
#   * buckets.py      — the shape-bucket ladder + batch-axis padding:
#     every dispatch shape is rounded up a small geometric ladder so the
#     jit cache stays bounded and a recompile is a counted event;
#   * compilewatch.py — process-wide backend-compile counter riding
#     jax.monitoring, the evidence behind the compile-cache discipline;
#   * scheduler.py    — the coalescing queue (max-wait/max-batch
#     admission), the bounded in-flight semaphore (backpressure), and
#     the process-default scheduler every oracle loop routes through.
###############################################################################
from mpisppy_tpu.dispatch.buckets import (   # noqa: F401
    BucketLadder,
    default_ladder,
    pad_qp_batch,
    slice_result,
)
from mpisppy_tpu.dispatch.compilewatch import CompileWatch  # noqa: F401
from mpisppy_tpu.dispatch.scheduler import (  # noqa: F401
    DispatchContext,
    DispatchOptions,
    SolveFailed,
    SolveScheduler,
    clear_session_context,
    configure,
    current_context,
    current_hub_iter,
    from_cfg,
    get_scheduler,
    scheduler_stats,
    set_hub_iter,
    set_session_context,
    solve_mip,
)
