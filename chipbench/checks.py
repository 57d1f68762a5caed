"""The comparison that decides ``correct``.

``max_err``: the largest slot error, over a sample of the window's
requests drawn from the seed, between the decrypted output and the
configuration's plain float64 reference on the same plaintexts.  The
reference imports nothing of the program.  Its limit is the one the
configuration file states under ``checks``; ``PERF.md`` gives the
readings it was set from.
"""
from __future__ import annotations

import numpy as np


def sample(setup, kept, n: int) -> list[tuple[int, dict]]:
    """``n`` of the kept requests, drawn from the seed, as (pool index,
    outputs of that request)."""
    reqs = []
    for i, outs in kept:
        for j, k in enumerate(setup.pick(i)):
            reqs.append((k, {t: cts[j] for t, cts in outs.items()}))
    pick = setup.check_rng.choice(len(reqs), size=min(n, len(reqs)),
                                  replace=False)
    return [reqs[int(p)] for p in sorted(pick)]


def run_checks(setup, kept, traffic: dict) -> dict:
    """Compare the sampled requests' outputs; ``correct`` holds when
    every number is within its limit (a NaN is not)."""
    cfg, prog = setup.cell.cfg, setup.cell.program
    limits = cfg["checks"]
    reqs = sample(setup, kept, traffic["check"])
    err = 0.0
    for k, outs in reqs:
        ref = prog.reference(setup.values[k], cfg)
        for tag, ct in outs.items():
            got = setup.ctx.decrypt(ct)
            err = max(err, float(np.max(np.abs(got - ref[tag]))))
    checks = {"max_err": {"value": err, "limit": limits["max_err"]}}
    correct = bool(reqs) and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    return {"correct": correct, "checks": checks}
