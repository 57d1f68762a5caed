"""Banded logistic-regression inference at the paper's ring, and its
plain reference.

``workloads.models.logreg`` built from its diagonals (a dense
2^15 x 2^15 matrix cannot be allocated): an 8-diagonal ``matvec_bsgs``
(baby steps of 4, hoisted), a bias ``pt_add`` and sigmoid(4t) as a
degree-15 Chebyshev interpolant by ``eval_chebyshev_bsgs``.  The weights
are drawn from the request pool's seed; a fresh ciphertext at level 35
is the input.  Sizes are in ``logreg-paper.json``.
"""
from __future__ import annotations

import numpy as np

INPUTS = ("x",)


def _weights(cfg: dict, nh: int):
    rng = np.random.default_rng(cfg["weight_seed"])
    vals = [rng.uniform(-1.0, 1.0, nh) for _ in range(cfg["n_diags"])]
    rowsum = np.sum(np.abs(vals), axis=0)
    diags = {d: v * cfg["gain"] / rowsum for d, v in enumerate(vals)}
    return diags, rng.uniform(-0.1, 0.1, nh)


def _sigmoid4(t):
    return 1.0 / (1.0 + np.exp(-4.0 * t))


def _cheb(cfg: dict) -> np.ndarray:
    """Chebyshev interpolant of sigmoid(4t) on [-1, 1] at the
    configuration's degree."""
    return np.polynomial.chebyshev.chebinterpolate(_sigmoid4, cfg["degree"])


def program(ctx, cts: dict, cfg: dict) -> dict:
    from repro.core import linear
    from repro.core.polyeval import eval_chebyshev_bsgs

    nh = ctx.params.num_slots
    diags, bias = _weights(cfg, nh)
    out = linear.matvec_bsgs(ctx, cts["x"], diags, bs=cfg["bs"])
    out = ctx.pt_add(out, ctx.encode(bias, level=out.level, scale=out.scale))
    return {"y": eval_chebyshev_bsgs(ctx, out, _cheb(cfg))}


def sample(rng: np.random.Generator, cfg: dict, nh: int) -> dict:
    return {"x": rng.uniform(-1.0, 1.0, nh)}


def reference(values: dict, cfg: dict) -> dict:
    """The layer in float64: banded matvec, bias, then the same
    Chebyshev interpolant of sigmoid(4t)."""
    x = values["x"]
    diags, bias = _weights(cfg, x.size)
    y = sum(v * np.roll(x, -d) for d, v in diags.items()) + bias
    return {"y": np.polynomial.chebyshev.chebval(y, _cheb(cfg))}
