###############################################################################
# SSLP: SIPLIB stochastic server location problem, generated natively as
# BoxQP scenario specs (no Pyomo).  Matches the reference model's
# semantics (ref:examples/sslp/model/ReferenceModel.py,
# ref:examples/sslp/sslp.py:27-60):
#
#   first stage:   FacilityOpen[j], j=1..n servers   (binary; the nonants)
#   second stage:  Allocation[i,j] (binary), Dummy[j] >= 0 (overflow)
#   constraints:   capacity:  sum_i Demand[i,j]*y_ij - d_j - Cap*x_j <= 0
#                  client:    sum_j y_ij == ClientPresent_i   (random RHS)
#   objective:     sum_j FixedCost_j x_j + Penalty*sum_j d_j
#                  - sum_ij Revenue_ij y_ij
#
# Randomness is RHS-only (ClientPresent), so the constraint matrix is
# DETERMINISTIC and shared across the whole batch — the batch compiler
# keeps one (m,n) `A` that broadcasts over scenarios, so HBM holds one
# copy of the matrix for any scenario count (the TPU answer to "sslp at
# 10k scenarios must fit").
#
# Data sources, in priority order:
#   * `data_dir`: a directory of SIPLIB `ScenarioK.dat` AMPL-format data
#     files (the reference's on-disk format,
#     ref:examples/sslp/data/sslp_*/scenariodata/) — parsed natively;
#   * `instance` params (n_servers, n_clients, seed): a seeded synthetic
#     instance following the SIPLIB generation scheme (Ntaimo & Sen):
#     integer revenues/demands U{0..25}, fixed costs U{40..70},
#     ClientPresent ~ Bernoulli(1/2).
#
# Integrality is carried as a mask and relaxed at solve time
# (LP relaxation), per the framework's kernel contract
# (ref:mpisppy/spopt.py:884 leans on MIP solvers; we use LP + rounding
# heuristics in the xhat plane).
###############################################################################
from __future__ import annotations

import os
import re

import numpy as np

from mpisppy_tpu.core.batch import ScenarioSpec
from mpisppy_tpu.utils.sputils import extract_num  # noqa: F401 (re-export)

DEFAULT_PENALTY = 1000.0

# data_dir -> the first parsed scenario's dict, reused as the shared
# deterministic-instance carrier for _build_spec's cache (see below)
_DATA_DIR_CACHE: dict[str, dict] = {}


# --------------------------------------------------------------------------
# AMPL .dat parsing (the subset SIPLIB sslp files use: scalar params,
# indexed-list params, and table params).
# --------------------------------------------------------------------------
def parse_dat(path: str) -> dict:
    """Parse an sslp AMPL-format .dat file into plain python/numpy data."""
    with open(path) as f:
        text = f.read()
    text = re.sub(r"#.*", "", text)
    out: dict = {}
    # Each statement ends with ';'
    for stmt in text.split(";"):
        stmt = stmt.strip()
        if not stmt.startswith("param"):
            continue
        body = stmt[len("param"):].strip()
        if ":=" in body and ":" in body.split(":=")[0]:
            # table form: "Name:\n  col1 col2 ... :=\n row v v v ..."
            name, rest = body.split(":", 1)
            name = name.strip()
            header, data = rest.split(":=", 1)
            cols = [int(tok) for tok in header.split()]
            rows: dict[int, list[float]] = {}
            toks = data.split()
            i = 0
            while i < len(toks):
                r = int(toks[i])
                vals = [float(v) for v in toks[i + 1:i + 1 + len(cols)]]
                rows[r] = vals
                i += 1 + len(cols)
            nr, nc = max(rows), max(cols)
            mat = np.zeros((nr, nc))
            for r, vals in rows.items():
                for cix, v in zip(cols, vals):
                    mat[r - 1, cix - 1] = v
            out[name] = mat
        else:
            name, data = body.split(":=", 1)
            name = name.strip()
            toks = data.split()
            if len(toks) == 1:
                out[name] = float(toks[0])
            else:
                idx = [int(t) for t in toks[0::2]]
                vals = [float(t) for t in toks[1::2]]
                vec = np.zeros(max(idx))
                for i_, v in zip(idx, vals):
                    vec[i_ - 1] = v
                out[name] = vec
    return out


# --------------------------------------------------------------------------
# Synthetic SIPLIB-style instances (seeded, reproducible).
# --------------------------------------------------------------------------
def synthetic_instance(n_servers: int, n_clients: int, seed: int = 0) -> dict:
    """Deterministic instance data following the SIPLIB generation ranges."""
    rng = np.random.RandomState(seed)
    demand = rng.randint(0, 26, size=(n_clients, n_servers)).astype(float)
    inst = {
        "NumServers": float(n_servers),
        "NumClients": float(n_clients),
        "FixedCost": rng.randint(40, 71, size=n_servers).astype(float),
        # SIPLIB instances use Revenue == Demand
        "Revenue": demand,
        "Demand": demand,
        # capacity sized so a handful of servers can cover expected demand
        "Capacity": float(
            np.ceil(1.5 * demand.mean() * n_clients / max(2, n_servers // 2))),
        "Penalty": DEFAULT_PENALTY,
    }
    return inst


def synthetic_client_present(n_clients: int, scennum: int,
                             seedoffset: int = 0) -> np.ndarray:
    """ClientPresent ~ Bernoulli(1/2) per client, seeded per scenario."""
    rng = np.random.RandomState(10_000 + scennum + seedoffset)
    return (rng.rand(n_clients) < 0.5).astype(float)


# --------------------------------------------------------------------------
# Scenario compiler: instance data + ClientPresent -> ScenarioSpec.
# Column layout (n = NumServers, m = NumClients):
#   [0:n)        x_j FacilityOpen     [0,1] int   <- nonants
#   [n:n+m*n)    y_ij Allocation      [0,1] int   (i-major: y[i,j])
#   [n+m*n: +n)  d_j Dummy            [0,inf)
# Row layout:
#   [0:n)        capacity rows:  sum_i D_ij y_ij - d_j - Cap x_j <= 0
#   [n:n+m)      client rows:    sum_j y_ij == h_i
# --------------------------------------------------------------------------
def _build_spec(inst: dict, client_present: np.ndarray,
                name: str, probability: float | None,
                strengthen: bool = False) -> ScenarioSpec:
    n = int(inst["NumServers"])
    m = int(inst["NumClients"])
    cache_key = "_spec_cache_vub" if strengthen else "_spec_cache"

    # The deterministic data (A, c, box, integrality) is identical for
    # every scenario of an instance — build it once and share the SAME
    # numpy objects across specs, so a 100k-scenario build costs O(m*n)
    # host memory, not O(S*m*n), and the batch compiler's shared-A
    # detection hits the identity fast path.
    cache = inst.get(cache_key)
    if cache is None:
        cap = float(inst["Capacity"])
        penalty = float(inst.get("Penalty", DEFAULT_PENALTY))
        D = np.asarray(inst["Demand"], float)        # (m, n)
        R = np.asarray(inst["Revenue"], float)       # (m, n)
        fc = np.asarray(inst["FixedCost"], float)    # (n,)

        ncols = n + m * n + n
        nrows = n + m

        c = np.concatenate([fc, -R.reshape(-1), np.full(n, penalty)])

        A = np.zeros((nrows, ncols))
        # capacity rows (one per server j)
        j = np.arange(n)
        A[j, j] = -cap                               # -Cap * x_j
        for jj in range(n):
            A[jj, n + jj:n + m * n:n] = D[:, jj]     # D_ij y_ij (i-major)
        A[j, n + m * n + j] = -1.0                   # -d_j

        l = np.zeros(ncols)  # noqa: E741
        # d_j only absorbs D·y_j - Cap x_j <= sum_i D_ij, so the natural
        # finite bound is the column demand sum; finite boxes everywhere
        # make every ops.boxqp.certified_dual_bound finite (the exact-MIP
        # branch-and-bound prunes on it)
        u = np.concatenate([np.ones(n + m * n), D.sum(axis=0)])

        # client rows (one per client i): sum_j y_ij == h_i
        for i in range(m):
            A[n + i, n + i * n:n + (i + 1) * n] = 1.0

        integer = np.zeros(ncols, bool)
        integer[:n + m * n] = True
        if strengthen:
            # variable-upper-bound strengthening y_ij <= x_j: valid for
            # every integer point (capacity already forces y=0 at x=0)
            # but cuts the fractional LP points where a barely-open
            # server serves clients — the standard SSLP tightening; it
            # lifts the LP relaxation toward the integer hull, so every
            # node LP in the exact-MIP plane (ops/bnb.py) prunes harder
            # and the integer-Lagrangian bound certifies tighter.  The
            # VUB rows have 2 nonzeros each, so the strengthened matrix
            # goes out SPARSE (ELL path: max row nnz ~ m+2 vs 705 dense
            # columns — the extra rows come nearly free).
            import scipy.sparse as sps
            V = np.zeros((m * n, ncols))
            rows = np.arange(m * n)
            V[rows, n + rows] = 1.0                  # +y_ij
            V[rows, np.tile(np.arange(n), m)] = -1.0  # -x_j (i-major y)
            A = sps.csr_matrix(np.vstack([A, V]))
        cache = inst[cache_key] = (A, c, l, u, integer)
    A, c, l, u, integer = cache

    nrows = A.shape[0]
    bl = np.full(nrows, -np.inf)
    bu = np.full(nrows, np.inf)
    bu[:n] = 0.0
    bl[n:n + m] = client_present
    bu[n:n + m] = client_present
    if strengthen:
        bu[n + m:] = 0.0  # y_ij - x_j <= 0

    return ScenarioSpec(
        name=name, c=c, A=A, bl=bl, bu=bu, l=l, u=u,
        nonant_idx=np.arange(n, dtype=np.int32),
        probability=probability, integer=integer,
    )


def scenario_creator(scenario_name: str, data_dir: str | None = None,
                     instance: dict | None = None,
                     n_servers: int = 5, n_clients: int = 25,
                     num_scens: int | None = None,
                     seedoffset: int = 0, inst_seed: int = 0,
                     lp_relax: bool = False,
                     strengthen: bool = False) -> ScenarioSpec:
    """ref:examples/sslp/sslp.py:27-45 semantics: one spec per scenario;
    `data_dir` points at SIPLIB scenariodata; otherwise synthetic.
    `lp_relax` drops the integrality mask (the BASELINE 'sslp LP-relaxed'
    configs), so xhat heuristics do not round.  `strengthen` adds the
    y_ij <= x_j variable-upper-bound rows (tighter LP relaxation for
    the exact-MIP certification plane)."""
    if data_dir is not None:
        data = parse_dat(os.path.join(data_dir, scenario_name + ".dat"))
        h = np.zeros(int(data["NumClients"]))
        cp = data.get("ClientPresent")
        if cp is not None:
            cp = np.asarray(cp, float).reshape(-1)
            h[:cp.shape[0]] = cp
        else:
            h[:] = 1.0  # AMPL default=1 (ReferenceModel.py ClientPresent)
        # The deterministic data repeats in every ScenarioK.dat — route
        # all scenarios of a directory through ONE cached inst dict so
        # _build_spec's shared-(A,c,…) cache actually hits and the batch
        # compiler sees identical array objects (one (m,n) A on the host
        # regardless of scenario count).
        inst = _DATA_DIR_CACHE.setdefault(data_dir, data)
    else:
        if instance is None:
            instance = synthetic_instance(n_servers, n_clients, inst_seed)
        h = synthetic_client_present(int(instance["NumClients"]),
                                     extract_num(scenario_name), seedoffset)
    prob = None if num_scens is None else 1.0 / num_scens
    spec = _build_spec(inst if data_dir is not None else instance, h,
                       scenario_name, prob, strengthen=strengthen)
    if lp_relax:
        spec.integer = np.zeros_like(spec.integer)  # shared: don't mutate
    return spec


# --------------------------------------------------------------------------
# Seeded scenario synthesis (scengen branch; docs/scengen.md).
#
# sslp randomness is RHS-only (ClientPresent), so the program's varying
# fields are just (bl, bu): the dense constraint matrix, costs, and box
# stay one shared template for ANY scenario count — the ideal shape for
# on-device synthesis.  ClientPresent ~ Bernoulli(1/2) per client draws
# from threefry (uniform(scen_key(base_key, s)) < 0.5) instead of the
# legacy RandomState stream.
# --------------------------------------------------------------------------
def scenario_program(num_scens: int, seed: int = 0, start: int = 0,
                     n_servers: int = 5, n_clients: int = 25,
                     inst_seed: int = 0, lp_relax: bool = False,
                     instance: dict | None = None):
    """ScenarioProgram drawing ClientPresent through scengen keys."""
    import jax
    import jax.numpy as jnp

    from mpisppy_tpu.scengen.program import (
        ScenarioProgram, scen_key, uniform_at,
    )

    inst = instance if instance is not None \
        else synthetic_instance(n_servers, n_clients, inst_seed)
    n = int(inst["NumServers"])
    m = int(inst["NumClients"])
    # populate the deterministic-structure cache and reuse its arrays
    _build_spec(inst, np.zeros(m), "_scengen_template", None)
    A, c, l, u, integer = inst["_spec_cache"]  # noqa: E741
    nrows = A.shape[0]

    bl0 = np.full(nrows, -np.inf)
    bu0 = np.full(nrows, np.inf)
    bu0[:n] = 0.0

    bl0_f = jnp.asarray(bl0, jnp.float32)
    bu0_f = jnp.asarray(bu0, jnp.float32)

    def sampler(base_key, idx):
        # row r = n + i draws ClientPresent_i from counter i; elementwise
        # over the full row vector (no scatter), so the sampler also
        # lowers inside the Pallas tile synth
        rows = jax.lax.iota(jnp.int32, nrows)
        client = (rows >= n) & (rows < n + m)
        u = uniform_at(scen_key(base_key, idx),
                       (rows - n).astype(jnp.uint32))
        h = (u < 0.5).astype(jnp.float32)
        return {"bl": jnp.where(client, h, bl0_f),
                "bu": jnp.where(client, h, bu0_f)}

    integer_eff = np.zeros_like(integer) if lp_relax else integer
    return ScenarioProgram(
        name="sslp", num_scenarios=int(num_scens),
        base_seed=int(seed), start=int(start),
        template={"c": c, "A": A, "bl": bl0, "bu": bu0, "l": l, "u": u},
        varying=("bl", "bu"), sampler=sampler,
        nonant_idx=np.arange(n, dtype=np.int32),
        integer=integer_eff,
    )


def scenario_names_creator(num_scens: int, start: int | None = None):
    """One-based names (ref:examples/sslp/sslp.py:55-60)."""
    start = 1 if start is None else start
    return [f"Scenario{i}" for i in range(start, start + num_scens)]


def inparser_adder(cfg):
    cfg.add_to_config("instance_name",
                      description="sslp instance name (e.g., sslp_15_45_10)",
                      domain=str, default=None)
    cfg.add_to_config("sslp_data_path",
                      description="path to sslp data (e.g., ./data)",
                      domain=str, default=None)
    cfg.add_to_config("n_servers", description="synthetic servers",
                      domain=int, default=5)
    cfg.add_to_config("n_clients", description="synthetic clients",
                      domain=int, default=25)
    cfg.add_to_config("sslp_lp_relax",
                      description="drop the integrality mask (the "
                      "BASELINE 'sslp LP-relaxed' configuration; serve "
                      "sessions use it for interactive-latency runs)",
                      domain=bool, default=False)


def kw_creator(cfg):
    lp_relax = bool(cfg.get("sslp_lp_relax", False))
    inst = cfg.get("instance_name")
    if inst is not None and cfg.get("sslp_data_path") is not None:
        ns = int(inst.split("_")[-1])
        data_dir = os.path.join(cfg["sslp_data_path"], inst, "scenariodata")
        return {"data_dir": data_dir, "num_scens": ns,
                "lp_relax": lp_relax}
    # build the synthetic instance ONCE and share it across every
    # scenario_creator call, so the dense constraint matrix exists once
    # on the host and the batch compiler's identity fast path fires
    return {"instance": synthetic_instance(cfg.get("n_servers", 5),
                                           cfg.get("n_clients", 25)),
            "num_scens": cfg.get("num_scens"),
            "lp_relax": lp_relax}


def scenario_denouement(rank, scenario_name, spec, x=None):
    pass


# --------------------------------------------------------------------------
# Exact integer recourse evaluation (the inner-bound evaluator).
#
# With the first stage FIXED, a scenario's recourse is an assignment
# with capacity-overflow penalties.  The batched B&B's generic dive
# rounds mid-face LP points and lands on poor assignments (the round-3
# documented blocker for the certified-gap inner side), so the sslp
# family carries its own exact evaluator: solve the recourse LP with
# the framework kernel, round each present client to its argmax server
# (the client rows are SOS1-like equalities), then 1-opt reassign until
# stable.  The returned value is the EXACT objective of an integral
# feasible recourse — a certified inner bound contribution — computed
# in closed form from the instance data.
# --------------------------------------------------------------------------
def exact_recourse_value(inst: dict, client_present: np.ndarray,
                         xhat: np.ndarray,
                         y_lp: np.ndarray | None = None) -> float:
    """One scenario's exact integer recourse value at first stage
    `xhat` ((n,) 0/1).  `y_lp` ((m, n) LP allocation, client-major)
    seeds the rounding; greedy best-revenue seeding is used without it.
    Serving from closed servers is allowed (original penalty-form
    semantics) but never chosen by the heuristic unless no server is
    open."""
    n = int(inst["NumServers"])
    m = int(inst["NumClients"])
    cap = float(inst["Capacity"])
    pen = float(inst.get("Penalty", DEFAULT_PENALTY))
    D = np.asarray(inst["Demand"], float)      # (m, n)
    R = np.asarray(inst["Revenue"], float)
    fc = np.asarray(inst["FixedCost"], float)
    x = np.round(np.asarray(xhat, float)[:n])
    open_j = np.nonzero(x > 0.5)[0]
    present = np.nonzero(np.asarray(client_present, float) > 0.5)[0]
    first = float(fc @ x)
    if present.size == 0:
        return first
    serve_set = open_j if open_j.size else np.arange(n)

    # seed assignment
    assign = np.empty(present.size, int)
    if y_lp is not None:
        for k, i in enumerate(present):
            assign[k] = serve_set[int(np.argmax(y_lp[i, serve_set]))]
    else:
        for k, i in enumerate(present):
            assign[k] = serve_set[int(np.argmax(R[i, serve_set]))]

    def value(assign):
        load = np.zeros(n)
        rev = 0.0
        for k, i in enumerate(present):
            j = assign[k]
            load[j] += D[i, j]
            rev += R[i, j]
        over = np.maximum(0.0, load - cap * x)
        return first - rev + pen * float(over.sum())

    best = value(assign)
    # 1-opt moves + pairwise swaps: single-client moves cannot fix
    # capacity packing (two clients on over-full servers may need to
    # trade places), so the sweep alternates move and swap passes
    improved = True
    sweeps = 0
    while improved and sweeps < 30:
        improved = False
        sweeps += 1
        for k in range(present.size):
            cur = assign[k]
            for j in serve_set:
                if j == cur:
                    continue
                trial = assign.copy()
                trial[k] = j
                v = value(trial)
                if v < best - 1e-9:
                    assign, best = trial, v
                    improved = True
        for k1 in range(present.size):
            for k2 in range(k1 + 1, present.size):
                if assign[k1] == assign[k2]:
                    continue
                trial = assign.copy()
                trial[k1], trial[k2] = assign[k2], assign[k1]
                v = value(trial)
                if v < best - 1e-9:
                    assign, best = trial, v
                    improved = True
    return best


def eval_candidates_exact(inst: dict, client_presents: "list[np.ndarray]",
                          xhats, probs=None,
                          lp_opts=None) -> "list[float]":
    """Exact integer inner-bound values E[f(xhat)] for several candidate
    first stages: one batched LP solve over (K*S) recourse problems via
    the framework kernel seeds per-client argmax rounding + 1-opt.
    Returns one expectation per candidate."""
    import dataclasses as _dc

    import jax.numpy as jnp

    from mpisppy_tpu.core import batch as batch_mod
    from mpisppy_tpu.ops import pdhg

    S = len(client_presents)
    K = len(xhats)
    n = int(inst["NumServers"])
    m = int(inst["NumClients"])
    if probs is None:
        probs = np.full(S, 1.0 / S)
    # one batched LP: scenarios repeat K times with different fixed x
    specs = [_build_spec(inst, client_presents[s], f"p{k}_{s}", None)
             for k in range(K) for s in range(S)]
    # uniform pair probabilities keep from_specs happy; expectations are
    # computed per candidate below
    for sp in specs:
        sp.probability = 1.0 / len(specs)
    b = batch_mod.from_specs(specs)
    xh = jnp.asarray(np.repeat(np.asarray(xhats, float), S, axis=0),
                     b.qp.c.dtype)  # (K*S, n)
    qp = b.with_fixed_nonants(xh)
    opts = lp_opts or pdhg.PDHGOptions(tol=1e-5, max_iters=20_000,
                                       restart_period=40, omega0=0.1)
    st = pdhg.solve(qp, opts, pdhg.init_state(qp, opts))
    # original-space allocation block, client-major (m, n) per problem
    x_orig = np.asarray(st.x) * np.broadcast_to(
        np.asarray(b.d_col), (K * S, b.qp.n))
    y_all = x_orig[:, n:n + m * n].reshape(K * S, m, n)
    out = []
    for k in range(K):
        tot = 0.0
        for s in range(S):
            tot += probs[s] * exact_recourse_value(
                inst, client_presents[s], np.asarray(xhats[k]),
                y_lp=y_all[k * S + s])
        out.append(float(tot))
    return out
