"""The one traffic generator: scenario samples for a closed loop of solves.

A traffic file (perfbench/traffic/<name>.json) gives the scenarios per
solve, the gap a solve must certify and its iteration cap; the
configuration gives the instance.  Solves run back to back, one client,
a closed loop.  Solve k of a run draws its scenario
sample from (seed, k); the warm-up solve of set-up draws from (seed, -1).
Everything is vectorised: one (S, clients) draw, no per-scenario loop.
"""
from __future__ import annotations

import numpy as np

#: traffic keys every file must carry, with their types
REQUIRED = {"scenarios": int, "rel_gap": float, "max_iterations": int,
            "unit": str, "warmup_iterations": int, "check": dict,
            "trace": dict}


def validate(traffic: dict, name: str) -> dict:
    """Refuse a traffic file that the generator cannot drive."""
    for key, typ in REQUIRED.items():
        if key not in traffic:
            raise ValueError(f"traffic {name}: missing key {key!r}")
        if typ is float and isinstance(traffic[key], int):
            continue
        if not isinstance(traffic[key], typ):
            raise ValueError(f"traffic {name}: {key} must be {typ.__name__}")
    if traffic["unit"] not in ("solve", "iteration"):
        raise ValueError(f"traffic {name}: unit must be solve or iteration")
    return traffic


def rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one stream of one run.  Any whole seed works: the
    seed sequence takes arbitrarily large integers."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % (1 << 64), stream + 1]))


def siplib_instance(inst_cfg: dict) -> dict:
    """The fixed instance of a configuration: the SIPLIB sslp generation
    ranges (integer demands U{0..25} with revenue = demand, fixed costs
    U{40..70}), with the capacity rule and draw order of the repository's
    models/sslp.py synthetic_instance, so the same instance seed gives
    the same instance."""
    if inst_cfg["generator"] != "siplib_synthetic":
        raise ValueError(f"unknown instance generator {inst_cfg['generator']}")
    n, m = int(inst_cfg["n_servers"]), int(inst_cfg["n_clients"])
    r = np.random.RandomState(int(inst_cfg["instance_seed"]))
    demand = r.randint(0, 26, size=(m, n)).astype(float)
    fixed = r.randint(40, 71, size=n).astype(float)
    return {
        "NumServers": float(n), "NumClients": float(m),
        "FixedCost": fixed, "Revenue": demand, "Demand": demand,
        "Capacity": float(np.ceil(1.5 * demand.mean() * m
                                  / max(2, n // 2))),
        "Penalty": float(inst_cfg["penalty"]),
    }


def client_present(inst_cfg: dict, scenarios: int, seed: int,
                   solve: int) -> np.ndarray:
    """(scenarios, clients) 0/1 float32: ClientPresent ~ Bernoulli(p),
    drawn for solve `solve` of the run with seed `seed`."""
    m = int(inst_cfg["n_clients"])
    u = rng(seed, solve).random((scenarios, m))
    return (u < float(inst_cfg["client_present_p"])).astype(np.float32)
