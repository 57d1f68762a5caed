"""One HELR training iteration at the paper's ring, and its plain reference.

Han et al., "Logistic Regression on Homomorphic Encrypted Data at
Scale" (AAAI 2019), as the HE^2 paper's HELR benchmark.  One ciphertext
``X`` packs ``n`` samples of ``f`` features, one sample per row of ``f``
slots, each label folded into its sample (``z_i = y_i x_i``); ``w``
holds the ``f`` weights repeated in every row.  Both arrive at level
``input_level``, where a bootstrap would leave them, and one iteration
of gradient ascent on the log-likelihood,

    w <- w + (gamma / n) sum_i sigmoid(-z_i . w) z_i,

runs as

    t    = RotSum(X * w, 1, 2, ..., f/2)     inner products at column 0
    t    = Rep(t * e0, -1, -2, ..., -f/2)    masked, copied along the row
    sig  = c0 + c1 t + c3 t^3                sigmoid(-t), gamma/n folded in
    grad = RotSum(sig * X, f, 2f, ..., nf/2) summed over the samples
    w2   = w + grad

with single, unhoisted rotations and the relinearizations of four
CMults.  The scale of the last steps is chosen so that ``grad`` lands on
``w``'s scale without a level of its own.  Sizes are in
``helr-paper.json``.
"""
from __future__ import annotations

import numpy as np

INPUTS = ("X", "w")


def coefficients(cfg: dict) -> tuple[float, float, float]:
    """(c0, c1, c3) of (gamma/n) sigmoid(-t) under HELR's cubic fit."""
    a0, a1, a3 = cfg["sigmoid"]
    lr = cfg["lr"]
    return lr * a0, -lr * a1, -lr * a3


def rotations(cfg: dict, nh: int) -> dict:
    """The three rotate-and-sum step lists of one iteration."""
    f = cfg["features"]
    within = [1 << j for j in range(f.bit_length() - 1)]
    return {"sum": within, "rep": [-s for s in within],
            "samples": [f << j for j in range((nh // f).bit_length() - 1)]}


def _rot_sum(ctx, ct, steps):
    for s in steps:
        ct = ctx.add(ct, ctx.rotate(ct, s))
    return ct


def _mul_vec(ctx, ct, vec, target_scale):
    """ct * vec, with the product's post-rescale scale forced to
    ``target_scale``."""
    q_last = ctx.chain(ct.level)[-1]
    pt = ctx.encode(vec, level=ct.level,
                    scale=target_scale * q_last / ct.scale)
    out = ctx.pt_mul(ct, pt, rescale=True)
    out.scale = target_scale
    return out


def program(ctx, cts: dict, cfg: dict) -> dict:
    """The iteration on ``ctx``: a ``TraceContext`` (to compile it) or a
    ``CKKSContext`` (to run it eagerly)."""
    from repro.core.polyeval import add_const, mul_const

    nh = ctx.params.num_slots
    rots = rotations(cfg, nh)
    c0, c1, c3 = coefficients(cfg)
    X, w = cts["X"], cts["w"]
    t = _rot_sum(ctx, ctx.multiply(X, w), rots["sum"])
    e0 = np.zeros(nh)
    e0[::cfg["features"]] = 1.0
    t = _rot_sum(ctx, _mul_vec(ctx, t, e0, w.scale), rots["rep"])
    t2 = ctx.multiply(t, t)
    # sig * X, two levels below v, lands on w's scale
    q = ctx.chain(t2.level - 1)
    v = add_const(ctx, mul_const(ctx, t2, c3,
                                 target_scale=q[-1] * q[-2] / t.scale), c1)
    sig = add_const(ctx, ctx.multiply(ctx.level_down(t, v.level), v), c0)
    grad = _rot_sum(ctx, ctx.multiply(sig, ctx.level_down(X, sig.level)),
                    rots["samples"])
    return {"w2": ctx.add(ctx.level_down(w, grad.level), grad)}


def sample(rng: np.random.Generator, cfg: dict, nh: int) -> dict:
    """Plaintext inputs of one request: features uniform in
    [-x_range, x_range], the f weights uniform in [-w_range, w_range]."""
    f = cfg["features"]
    x = rng.uniform(-cfg["x_range"], cfg["x_range"], nh)
    w = rng.uniform(-cfg["w_range"], cfg["w_range"], f)
    return {"X": x, "w": np.tile(w, nh // f)}


def reference(values: dict, cfg: dict) -> dict:
    """The iteration in float64 on the plaintexts, by rows."""
    c0, c1, c3 = coefficients(cfg)
    f = cfg["features"]
    Z = values["X"].reshape(-1, f)
    w = values["w"]
    t = Z @ w[:f]
    grad = (c0 + c1 * t + c3 * t ** 3) @ Z
    return {"w2": w + np.tile(grad, Z.shape[0])}
