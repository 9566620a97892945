# graftlint IR layer (ISSUE 15; tools/graftlint/ir/,
# docs/static_analysis.md "IR layer"): seeded leaky fixture kernels one
# per IR pass, the clean-repo fast-subset CLI run (empty baseline,
# budget-asserted), the KERNEL_IR.json regen-vs-committed gate + the
# synthetic-regression exit-2 proof, the lowering-cache round trip, and
# compile-count regression tests proving the audited kernels really do
# run 0 recompiles across same-shape different-value inputs.
from __future__ import annotations

import copy
import json
import os
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from tools.graftlint.ir import manifest as ir_manifest  # noqa: E402
from tools.graftlint.ir import passes as ir_passes  # noqa: E402

IR_RULE_NAMES = ("ir-const-capture,ir-dtype-census,ir-host-boundary,"
                 "ir-collective-manifest,ir-memory-high-water")


def _sub_env(cache_dir=None):
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "HOME": os.path.expanduser("~"),
           "JAX_PLATFORMS": "cpu"}
    if cache_dir is not None:
        env["GRAFTLINT_IR_CACHE"] = str(cache_dir)
    return env


@pytest.fixture(scope="module")
def ir_cache(tmp_path_factory):
    """One lowering cache shared by this module's subprocess runs —
    the second drive costs traces, not compiles (the jaxpr-hash cache
    CI and local runs share via --ir-cache / GRAFTLINT_IR_CACHE)."""
    return tmp_path_factory.mktemp("ir_cache")


# ---------------------------------------------------------------------------
# tier-1 wiring: the repo lints CLEAN on the fast manifest subset,
# with an EMPTY baseline, inside the time budget
# ---------------------------------------------------------------------------
def test_ir_fast_subset_repo_lints_clean_within_budget(ir_cache):
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--json",
         "--rules", IR_RULE_NAMES, "--ir-subset", "fast"],
        capture_output=True, text=True, cwd=REPO,
        env=_sub_env(ir_cache), timeout=300)
    elapsed = time.monotonic() - t0
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    rep = json.loads(out.stdout)
    assert rep["ok"] and rep["errors"] == []
    active = [f for f in rep["findings"] if not f["baselined"]]
    assert active == [], active
    # EMPTY baseline: nothing grandfathered on any IR rule
    assert rep["baselined"] == 0
    # the tier-1 budget the ISSUE sets — cached lowerings hold it
    assert elapsed < 60.0, f"fast IR subset took {elapsed:.1f}s"


def test_kernel_ir_fast_regen_matches_committed(ir_cache):
    """Regenerate the fast-subset facts and gate them against the
    committed KERNEL_IR.json — const bytes may never grow, temp bytes
    ratchet at +10% (telemetry/regress.py GATES)."""
    from mpisppy_tpu.telemetry import regress
    out = subprocess.run(
        [sys.executable, "-m", "tools.graftlint.ir", "--subset", "fast"],
        capture_output=True, text=True, cwd=REPO,
        env=_sub_env(ir_cache), timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    fresh = json.loads(out.stdout)
    committed = regress.load_artifact(os.path.join(REPO, "KERNEL_IR.json"))
    rep = regress.gate(committed, fresh)
    assert rep["common"] > 0
    assert rep["ok"], regress.render_compare(rep, only_gated=True)


@pytest.mark.slow
def test_kernel_ir_full_sweep_matches_committed(tmp_path):
    """The full manifest sweep (every kernel, sharded collective facts)
    gates against the committed artifact and covers every kernel the
    artifact carries."""
    from mpisppy_tpu.telemetry import regress
    out = subprocess.run(
        [sys.executable, "-m", "tools.graftlint.ir", "--subset", "full"],
        capture_output=True, text=True, cwd=REPO,
        env=_sub_env(tmp_path), timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    fresh = json.loads(out.stdout)
    committed = regress.load_artifact(os.path.join(REPO, "KERNEL_IR.json"))
    assert set(fresh["kernels"]) == set(committed["kernels"])
    rep = regress.gate(committed, fresh)
    assert rep["ok"], regress.render_compare(rep, only_gated=True)


# ---------------------------------------------------------------------------
# regress wiring: synthetic regression exits 2; committed artifact
# witnesses the gate keys (the schema-drift coupling)
# ---------------------------------------------------------------------------
def test_kernel_ir_synthetic_regression_exits_2(tmp_path):
    with open(os.path.join(REPO, "KERNEL_IR.json")) as f:
        good = json.load(f)
    bad = copy.deepcopy(good)
    some = sorted(bad["kernels"])[0]
    bad["kernels"][some]["const_bytes"] += 4096      # any increase fails
    other = sorted(bad["kernels"])[-1]
    bad["kernels"][other]["temp_bytes"] = int(
        bad["kernels"][other]["temp_bytes"] * 1.2 + 64)  # past +10%
    bad_path = tmp_path / "KERNEL_IR_bad.json"
    bad_path.write_text(json.dumps(bad))
    out = subprocess.run(
        [sys.executable, "-m", "mpisppy_tpu.telemetry", "gate",
         "KERNEL_IR.json", str(bad_path), "--json"],
        capture_output=True, text=True, cwd=REPO, env=_sub_env(),
        timeout=120)
    assert out.returncode == 2, out.stdout[-1500:] + out.stderr[-500:]
    rep = json.loads(out.stdout)
    failed = {r["metric"] for r in rep["regressions"]}
    assert f"kernels.{some}.const_bytes" in failed
    assert f"kernels.{other}.temp_bytes" in failed


def test_committed_artifact_witnesses_gate_keys():
    """Schema-drift check 4 coupling: the kernels.*.const_bytes /
    temp_bytes GATES patterns must resolve against the committed
    KERNEL_IR.json — a gate nothing produces gates nothing."""
    import re
    from mpisppy_tpu.telemetry import regress
    keys = set(regress.extract_metrics(
        regress.load_artifact(os.path.join(REPO, "KERNEL_IR.json"))))
    for pat in (r"kernels\..*\.const_bytes$", r"kernels\..*\.temp_bytes$"):
        assert any(re.search(pat, k) for k in keys), pat
    # and the artifact covers the full manifest
    with open(os.path.join(REPO, "KERNEL_IR.json")) as f:
        art = json.load(f)
    assert set(art["kernels"]) == set(ir_manifest.names("full"))


# ---------------------------------------------------------------------------
# seeded leaky fixture kernels — one per IR pass, each asserted caught
# ---------------------------------------------------------------------------
def _fixture_audit(spec, **kw):
    from tools.graftlint.ir import audit
    return audit.audit_kernel(spec, ir_manifest.Fixtures(), REPO, **kw)


def test_const_capture_catches_closed_over_ndarray():
    import jax
    import jax.numpy as jnp
    import numpy as np
    baked = jnp.asarray(np.arange(1024, dtype=np.float32))  # 4 KiB

    def build(fx):
        return jax.jit(lambda x: x + baked), (jnp.zeros(1024),)

    spec = ir_manifest.KernelSpec("fixture_const", build)
    facts = _fixture_audit(spec)
    found = ir_passes.const_capture_findings(spec, facts)
    assert len(found) == 1 and "4096 bytes" in found[0].message
    assert found[0].key == "ir::fixture_const::const::float32[1024]#0"
    assert facts.const_bytes >= 4096


def test_const_capture_threshold_exempts_small_helpers():
    import jax
    import jax.numpy as jnp
    small = jnp.arange(8, dtype=jnp.float32)     # 32 bytes: idiomatic

    def build(fx):
        return jax.jit(lambda x: x + small), (jnp.zeros(8),)

    spec = ir_manifest.KernelSpec("fixture_small_const", build)
    facts = _fixture_audit(spec)
    assert ir_passes.const_capture_findings(spec, facts) == []
    assert facts.const_bytes == 32               # still in the ratchet


def test_dtype_census_catches_f64_promotion():
    import jax
    import jax.numpy as jnp

    def build(fx):
        return jax.jit(
            lambda x: (x.astype(jnp.float64) * 2.0).sum()), \
            (jnp.zeros(16, jnp.float32),)

    spec = ir_manifest.KernelSpec("fixture_f64", build)
    with jax.enable_x64():
        facts = _fixture_audit(spec)
    found = ir_passes.dtype_census_findings(spec, facts)
    assert len(found) == 1 and "float64" in found[0].message
    assert found[0].key == "ir::fixture_f64::f64"


def test_host_boundary_catches_io_callback():
    import jax
    import jax.numpy as jnp
    from jax.experimental import io_callback

    def kernel(x):
        io_callback(lambda v: None, None, x)
        return x * 2.0

    def build(fx):
        return jax.jit(kernel), (jnp.zeros(8),)

    spec = ir_manifest.KernelSpec("fixture_cb", build)
    facts = _fixture_audit(spec)
    found = ir_passes.host_boundary_findings(spec, facts)
    assert [f.key for f in found] == ["ir::fixture_cb::callback::io_callback"]


def test_memory_high_water_catches_s_major_temp():
    import jax
    import jax.numpy as jnp

    def kernel(key):
        big = jax.random.normal(key, (256, 256))     # 256 KiB S-major
        return (big @ big.T).sum()

    def build(fx):
        return jax.jit(kernel), (jax.random.PRNGKey(0),)

    spec = ir_manifest.KernelSpec("fixture_smear", build, virtual=True,
                                  temp_budget_bytes=4096)
    facts = _fixture_audit(spec)
    found = ir_passes.memory_high_water_findings(spec, facts)
    assert len(found) == 1 and "transients budget" in found[0].message
    # same kernel under an honest budget: clean
    ok_spec = ir_manifest.KernelSpec(
        "fixture_smear_ok", build, virtual=True,
        temp_budget_bytes=facts.temp_bytes)
    assert ir_passes.memory_high_water_findings(ok_spec, facts) == []


_COLLECTIVE_FIXTURE = r"""
import json, sys
sys.path.insert(0, {repo!r})
from tools.graftlint.ir import audit, manifest, passes
audit.ensure_devices(2)
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from mpisppy_tpu.parallel import mesh as mesh_mod


def _sharded(fx, x):
    if fx.mesh is not None:
        return jax.device_put(
            x, NamedSharding(fx.mesh, P(mesh_mod.SCEN_AXIS)))
    return x


def build_silent(fx):
    return jax.jit(lambda v: v + 1.0), (_sharded(fx, jnp.arange(
        8, dtype=jnp.float32)),)


def build_chatty(fx):
    return jax.jit(lambda v: v - v.mean()), (_sharded(fx, jnp.arange(
        8, dtype=jnp.float32)),)


silent = manifest.KernelSpec(
    "fixture_silent", build_silent, sharded=True,
    collectives=frozenset({{"all-reduce"}}))        # declared, absent
chatty = manifest.KernelSpec(
    "fixture_chatty", build_chatty, sharded=True,
    collectives=frozenset())                        # present, undeclared
fx = manifest.Fixtures()
sfx = manifest.Fixtures(mesh=mesh_mod.make_mesh(2))
keys = []
for spec in (silent, chatty):
    facts = audit.audit_kernel(spec, fx, {repo!r}, sharded_fx=sfx)
    keys += [f.key for f in passes.collective_manifest_findings(
        spec, facts)]
print(json.dumps(keys))
"""


def test_collective_manifest_catches_both_directions(tmp_path):
    """Declared-but-missing AND present-but-undeclared collectives are
    findings.  Runs in a subprocess: collective facts need >= 2 virtual
    devices forced before jax initializes."""
    out = subprocess.run(
        [sys.executable, "-c", _COLLECTIVE_FIXTURE.format(repo=REPO)],
        capture_output=True, text=True, cwd=REPO,
        env=_sub_env(tmp_path), timeout=180)
    assert out.returncode == 0, out.stderr[-2000:]
    keys = json.loads(out.stdout.strip().splitlines()[-1])
    assert "ir::fixture_silent::collective-missing::all-reduce" in keys
    assert "ir::fixture_chatty::collective-extra::all-reduce" in keys


# ---------------------------------------------------------------------------
# rule plumbing: scoped scans skip the audit; a broken audit is a
# finding on whichever selected IR rule runs first, never a clean exit
# ---------------------------------------------------------------------------
def test_ir_rules_skip_path_scoped_scans():
    from tools.graftlint.core import Context
    ctx = Context(REPO, paths=["mpisppy_tpu/telemetry"])
    assert ctx.scoped
    assert ir_passes._audit_for(ctx) is None
    for rule in ir_passes.IR_RULES:
        assert rule.run(ctx) == []


def test_ir_audit_failure_reported_on_first_selected_rule(monkeypatch):
    """A crashed audit must never read as a clean repo — even when the
    rule subset excludes ir-const-capture; and it reports exactly
    once."""
    from tools import graftlint
    from tools.graftlint.ir import audit as ir_audit_mod

    def boom(*a, **k):
        raise RuntimeError("synthetic audit failure")
    monkeypatch.setattr(ir_audit_mod, "run_manifest", boom)
    rep = graftlint.lint(
        REPO, rules=["ir-dtype-census", "ir-memory-high-water"])
    assert not rep["ok"]
    failed = [f for f in rep["findings"] if f["key"] == "ir-audit-failed"]
    assert len(failed) == 1
    assert failed[0]["rule"] == "ir-dtype-census"
    assert "synthetic audit failure" in failed[0]["message"]


# ---------------------------------------------------------------------------
# the jaxpr-hash lowering cache
# ---------------------------------------------------------------------------
def test_lowering_cache_round_trip(tmp_path):
    import jax
    import jax.numpy as jnp

    def build(fx):
        return jax.jit(lambda x: (x * 2.0).sum()), (jnp.zeros(32),)

    spec = ir_manifest.KernelSpec("fixture_cached", build)
    first = _fixture_audit(spec, cdir=str(tmp_path))
    assert not first.cached
    second = _fixture_audit(spec, cdir=str(tmp_path))
    assert second.cached
    assert (second.temp_bytes, second.arg_bytes, second.flops) == \
        (first.temp_bytes, first.arg_bytes, first.flops)


# ---------------------------------------------------------------------------
# CLI satellite: bare --rules lists IR rules with kernel counts
# ---------------------------------------------------------------------------
def test_cli_rules_listing_shows_ir_kernel_counts():
    out = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--rules"],
        capture_output=True, text=True, cwd=REPO, env=_sub_env(),
        timeout=60)
    assert out.returncode == 0, out.stderr
    text = out.stdout
    counts = ir_passes.kernel_counts()
    for rule, n in counts.items():
        line = next(ln for ln in text.splitlines() if ln.startswith(rule))
        assert f"[{n} kernels]" in line, line
    # AST rules list too, without counts
    assert any(ln.startswith("trace-purity") for ln in text.splitlines())


# ---------------------------------------------------------------------------
# compile-count regression tests: the audited (const-free) kernels run
# 0 recompiles across same-shape different-VALUE inputs — the dynamic
# counterpart of the ir-const-capture pass (and the missing coverage
# for the PR-4 leaks: estimate_norm and the bnb round kernels)
# ---------------------------------------------------------------------------
def _jitter(tree):
    """Same shapes/dtypes, fresh float values."""
    import jax
    import jax.numpy as jnp

    def bump(a):
        if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating):
            # dtype-typed scalars: a bare python float would promote a
            # numpy f32 leaf to f64 and change the aval (a recompile
            # for the WRONG reason — shapes, not values)
            one = a.dtype.type(1.001)
            eps = a.dtype.type(0.0009)
            return a * one + eps
        return a
    return jax.tree_util.tree_map(bump, tree)


def test_ph_iterk_zero_recompiles_across_values():
    import jax.numpy as jnp
    import __graft_entry__ as ge
    from mpisppy_tpu.algos import ph as ph_mod
    from mpisppy_tpu.dispatch import compilewatch
    batch = ge._flagship_batch(num_scens=6, crops_multiplier=1)
    opts = ph_mod.PHOptions(subproblem_windows=2, iter0_windows=4)
    rho = jnp.ones(batch.num_nonants, batch.qp.c.dtype)
    st, _, _ = ph_mod.ph_iter0(batch, rho, opts)
    jbatch = _jitter(batch)   # built BEFORE the watch: the eager bump
    #                           ops compile their own tiny executables
    st = ph_mod.ph_iterk(batch, st, opts)        # warm the shape key
    watch = compilewatch.CompileWatch()
    warm = watch.total()
    ph_mod.ph_iterk(jbatch, st, opts).conv.block_until_ready()
    assert watch.total() == warm, \
        "ph_iterk recompiled for same-shape different-value batch"


def test_xhat_evaluate_zero_recompiles_across_values():
    import __graft_entry__ as ge
    from mpisppy_tpu.algos import xhat as xhat_mod
    from mpisppy_tpu.dispatch import compilewatch
    from mpisppy_tpu.ops import pdhg
    batch = ge._flagship_batch(num_scens=6, crops_multiplier=1)
    opts = pdhg.PDHGOptions(tol=1e-4, max_iters=40, restart_period=10)
    lb, ub = batch.nonant_box()
    import jax.numpy as jnp
    xhat = jnp.asarray((lb + ub) / 2.0, jnp.float32)
    jbatch = _jitter(batch)
    xhat_mod._evaluate_core(batch, xhat, opts, 1e-3)      # warm
    watch = compilewatch.CompileWatch()
    warm = watch.total()
    res = xhat_mod._evaluate_core(jbatch, xhat, opts, 1e-3)
    res.value.block_until_ready()
    assert watch.total() == warm, \
        "_evaluate_core recompiled for same-shape different-value batch"


def test_estimate_norm_zero_recompiles_across_values():
    """The original PR-4 leak site: eager power iteration baked QP
    values into its fori_loop jaxpr — one backend compile per distinct
    QP.  Now jitted; prove the fix holds dynamically."""
    import __graft_entry__ as ge
    from mpisppy_tpu.dispatch import compilewatch
    from mpisppy_tpu.ops import pdhg
    qp = ge._sslp_batch(num_scens=4).qp
    jqp = _jitter(qp)
    pdhg.estimate_norm(qp).block_until_ready()            # warm
    watch = compilewatch.CompileWatch()
    warm = watch.total()
    pdhg.estimate_norm(jqp).block_until_ready()
    assert watch.total() == warm, \
        "estimate_norm recompiled for same-shape different-value QP"


def test_bnb_round_zero_recompiles_across_values():
    import __graft_entry__ as ge
    from mpisppy_tpu.dispatch import compilewatch
    from mpisppy_tpu.ops import bnb as bnb_mod
    from mpisppy_tpu.ops import pdhg
    sbatch = ge._sslp_batch(num_scens=4)
    bnb_opts = bnb_mod.BnBOptions(
        max_rounds=1, pump_rounds=0,
        lp=pdhg.PDHGOptions(tol=1e-3, max_iters=200))
    int_cols, bst = ge._bnb_probe_state(sbatch, bnb_opts)
    jqp = _jitter(sbatch.qp)
    out = bnb_mod.bnb_round(sbatch.qp, sbatch.d_col, int_cols, bst,
                            bnb_opts)                     # warm
    watch = compilewatch.CompileWatch()
    warm = watch.total()
    out = bnb_mod.bnb_round(jqp, sbatch.d_col, int_cols,
                            bst, bnb_opts)
    out.outer.block_until_ready()
    assert watch.total() == warm, \
        "bnb_round recompiled for same-shape different-value QP"
