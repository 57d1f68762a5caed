"""Relinearization as a keyswitch-family member.

Covers the relin acceptance gates:
  * engine ``relin`` bit-exact with keyswitch-then-add (and with the
    seed per-digit path) — CMults are just the other keyswitch flavor
  * eager vs engine vs vmap-batched CMult tally IDENTICAL ``OpCounters``
    (modup/moddown/ip counts and NTT/BConv/IP word volumes)
  * ``trace_counts`` stays flat across dispatches of the relin jit
    plans (one trace per (op, level, shape) plan)
  * ``multi_relin_sum`` closes n relins with ONE ModDown inside a
    measured error envelope (ARK-style lazy ModDown)
  * the BSGS Chebyshev evaluation compiles end to end: ``RelinStep``s
    bit-exact under ``exact=True``, ``MultiRelinStep`` merges the
    giant-step product sums under ``exact=False`` at fewer ModDowns,
    and predicted-vs-executed reconciliation stays exact
  * batched relin/rotation paths on backend='pallas' are bit-exact with
    the jnp backend (the kernel suite is vmap-compatible via
    ``custom_vmap`` rules; there is no batched-pallas gate any more)
"""
import numpy as np
import pytest

from repro.core.ckks import CKKSContext, Ciphertext, tensor_product
from repro.core.params import CKKSParams
from repro.dfg.graph import OpKind
from repro.core.polyeval import (
    cheb_divmod, chebyshev_coeffs, eval_chebyshev, eval_chebyshev_bsgs,
)
from repro.runtime import ProgramExecutor, TraceContext, compile_program
from repro.runtime.lower import MultiRelinStep, RelinStep

from parity import assert_program_parity, ct_equal as _ct_equal


@pytest.fixture(scope="module")
def relin_ctx():
    p = CKKSParams(logN=8, L=9, alpha=2, k=3, q_bits=29, scale_bits=29)
    return CKKSContext(p, seed=13)


@pytest.fixture(scope="module")
def cheb_case(relin_ctx):
    rng = np.random.default_rng(3)
    nh = relin_ctx.params.num_slots
    x = rng.uniform(-1, 1, nh)
    fn = lambda t: np.sin(2 * np.pi * 1.5 * t) / (2 * np.pi)  # noqa: E731
    coeffs = chebyshev_coeffs(fn, 15)
    return x, fn, coeffs


# ----------------------- engine relin parity -----------------------------

def test_cheb_divmod_identity():
    import numpy.polynomial.chebyshev as C

    rng = np.random.default_rng(0)
    for d, g in ((28, 16), (15, 8), (9, 8), (8, 8)):
        c = rng.normal(size=d + 1).astype(complex)
        q, r = cheb_divmod(c, g)
        x = np.linspace(-1, 1, 13)
        tg = [0] * g + [1]
        got = C.chebval(x, q) * C.chebval(x, tg) + C.chebval(x, r)
        assert np.abs(C.chebval(x, c) - got).max() < 1e-12
        assert len(r) == g


def test_relin_bitexact_with_seed_multiply(relin_ctx):
    """Engine CMult (jit relin plan) == seed per-digit CMult, bit for
    bit — the two dispatch paths of the keyswitch family agree."""
    ctx = relin_ctx
    rng = np.random.default_rng(7)
    nh = ctx.params.num_slots
    a = ctx.encrypt(rng.normal(size=nh))
    b = ctx.encrypt(rng.normal(size=nh))
    got = ctx.multiply(a, b, rescale=False)
    ctx.use_engine = False
    try:
        exp = ctx.multiply(a, b, rescale=False)
    finally:
        ctx.use_engine = True
    assert _ct_equal(got, exp)
    assert got.scale == exp.scale and got.level == exp.level


def test_relin_digits_interface(relin_ctx):
    """Pre-computed d2 digits (engine ``modup``) slot into ``relin``
    exactly like rotation digits — bit-exact with the internal ModUp."""
    ctx = relin_ctx
    rng = np.random.default_rng(8)
    nh = ctx.params.num_slots
    a = ctx.encrypt(rng.normal(size=nh))
    b = ctx.encrypt(rng.normal(size=nh))
    lvl = a.level
    mods = ctx.pc.mods(ctx.chain(lvl))
    d0, d1, d2 = tensor_product(a, b, mods, ctx.pc)
    key = ctx.keys.mult_key
    c0, c1 = ctx.engine.relin(d0, d1, d2, key, lvl)
    digits = ctx.engine.modup(d2, lvl)
    c0d, c1d = ctx.engine.relin(d0, d1, d2, key, lvl, digits=digits)
    assert np.array_equal(np.asarray(c0), np.asarray(c0d))
    assert np.array_equal(np.asarray(c1), np.asarray(c1d))


def test_counters_cmult_parity_eager_engine_batched(relin_ctx):
    """Seed, engine, and vmap-batched CMults tally identical per-ct
    counters — invocation counts AND plan-shape-derived word volumes."""
    ctx = relin_ctx
    rng = np.random.default_rng(9)
    nh = ctx.params.num_slots
    B = 3
    cts = [(ctx.encrypt(rng.normal(size=nh)),
            ctx.encrypt(rng.normal(size=nh))) for _ in range(B)]
    c = ctx.counters

    s0 = c.snapshot()
    for a, b in cts:
        ctx.multiply(a, b, rescale=False)
    engine_counts = c.delta(s0)

    ctx.use_engine = False
    try:
        s1 = c.snapshot()
        for a, b in cts:
            ctx.multiply(a, b, rescale=False)
        seed_counts = c.delta(s1)
    finally:
        ctx.use_engine = True
    assert engine_counts == seed_counts
    assert engine_counts.relin == B and engine_counts.modup == B

    # batched: one relin_batched dispatch covers all B ciphertexts
    lvl = cts[0][0].level
    mods = ctx.pc.mods(ctx.chain(lvl))
    import jax.numpy as jnp

    a_b = Ciphertext(jnp.stack([p[0].c0 for p in cts]),
                     jnp.stack([p[0].c1 for p in cts]), lvl,
                     cts[0][0].scale)
    b_b = Ciphertext(jnp.stack([p[1].c0 for p in cts]),
                     jnp.stack([p[1].c1 for p in cts]), lvl,
                     cts[0][1].scale)
    d0, d1, d2 = tensor_product(a_b, b_b, mods, ctx.pc)
    s2 = c.snapshot()
    c0b, c1b = ctx.engine.relin_batched(d0, d1, d2, ctx.keys.mult_key,
                                        lvl)
    batched_counts = c.delta(s2)
    assert batched_counts == engine_counts
    # and the values match the per-ct engine path bit for bit
    for i, (a, b) in enumerate(cts):
        exp = ctx.multiply(a, b, rescale=False)
        assert np.array_equal(np.asarray(c0b[i]), np.asarray(exp.c0))
        assert np.array_equal(np.asarray(c1b[i]), np.asarray(exp.c1))


def test_relin_trace_counts_flat_across_batches(relin_ctx):
    """Re-dispatching a relin jit plan at the same (level, shape) is a
    cache hit: ``trace_counts`` stays at one trace per plan."""
    ctx = relin_ctx
    rng = np.random.default_rng(10)
    nh = ctx.params.num_slots
    import jax.numpy as jnp

    lvl = ctx.params.L
    mods = ctx.pc.mods(ctx.chain(lvl))
    before = ctx.engine.trace_counts.get(("relin_b", lvl, False), 0)
    for B in (2, 2, 2):
        pairs = [(ctx.encrypt(rng.normal(size=nh)),
                  ctx.encrypt(rng.normal(size=nh))) for _ in range(B)]
        a_b = Ciphertext(jnp.stack([p[0].c0 for p in pairs]),
                         jnp.stack([p[0].c1 for p in pairs]), lvl, 1.0)
        b_b = Ciphertext(jnp.stack([p[1].c0 for p in pairs]),
                         jnp.stack([p[1].c1 for p in pairs]), lvl, 1.0)
        d0, d1, d2 = tensor_product(a_b, b_b, mods, ctx.pc)
        ctx.engine.relin_batched(d0, d1, d2, ctx.keys.mult_key, lvl)
    after = ctx.engine.trace_counts[("relin_b", lvl, False)]
    assert after - before == 1      # three same-shape dispatches: 1 trace


# ----------------------- multi-relin (ONE ModDown) -----------------------

def test_multi_relin_one_moddown(relin_ctx):
    """n CMult terms close with ONE ModDown; the merged sum stays within
    the deferred approximate-FBC rounding envelope of the exact sum."""
    ctx = relin_ctx
    rng = np.random.default_rng(11)
    nh = ctx.params.num_slots
    n = 3
    xs = [rng.normal(size=nh) * 0.3 for _ in range(2 * n)]
    pairs = [(ctx.encrypt(xs[2 * i]), ctx.encrypt(xs[2 * i + 1]))
             for i in range(n)]
    lvl = pairs[0][0].level
    mods = ctx.pc.mods(ctx.chain(lvl))
    c = ctx.counters

    s0 = c.snapshot()
    exact = None
    for a, b in pairs:
        t = ctx.multiply(a, b, rescale=False)
        exact = t if exact is None else ctx.add(exact, t)
    d_exact = c.delta(s0)

    s1 = c.snapshot()
    d0s, d1s, digs = [], [], []
    for a, b in pairs:
        d0, d1, d2 = tensor_product(a, b, mods, ctx.pc)
        d0s.append(d0)
        d1s.append(d1)
        digs.append(ctx.engine.modup(d2, lvl))
    c0, c1 = ctx.engine.multi_relin_sum(d0s, d1s, digs,
                                        ctx.keys.mult_key, lvl)
    d_multi = c.delta(s1)
    merged = Ciphertext(c0, c1, lvl, exact.scale)

    assert d_exact.moddown == n and d_multi.moddown == 1
    assert d_exact.modup == d_multi.modup == n
    assert d_exact.ip == d_multi.ip == n
    assert d_exact.relin == d_multi.relin == n
    assert d_multi.relin_blocks == 1
    assert not _ct_equal(merged, exact)     # genuinely different path

    # the deferred approximate-FBC roundings must not cost accuracy:
    # both paths decode the same plaintext product sum equally well
    ref = sum(xs[2 * i] * xs[2 * i + 1] for i in range(n))
    err_exact = np.abs(ctx.decrypt(exact).real - ref).max()
    err_multi = np.abs(ctx.decrypt(merged).real - ref).max()
    assert err_multi < err_exact * 1.5 + 1e-4, (err_multi, err_exact)


# ----------------------- compiled BSGS Chebyshev -------------------------

def _trace_cheb(params, coeffs):
    tc = TraceContext(params)
    h = tc.input("x", level=params.L, scale=params.scale)
    tc.output(eval_chebyshev_bsgs(tc, h, coeffs), "y")
    return tc


def test_bsgs_cheb_fewer_relins_same_accuracy(relin_ctx, cheb_case):
    """The giant-step evaluation needs O(sqrt d) CMults instead of the
    dense recurrence's O(d), at the same accuracy and output level."""
    ctx = relin_ctx
    x, fn, coeffs = cheb_case
    c = ctx.counters
    s0 = c.snapshot()
    dense = eval_chebyshev(ctx, ctx.encrypt(x), coeffs)
    d_dense = c.delta(s0)
    s1 = c.snapshot()
    bsgs = eval_chebyshev_bsgs(ctx, ctx.encrypt(x), coeffs)
    d_bsgs = c.delta(s1)
    assert d_bsgs.relin < d_dense.relin
    assert bsgs.level >= dense.level
    ref = fn(x)
    assert np.abs(ctx.decrypt(bsgs).real - ref).max() < 5e-3
    assert np.abs(ctx.decrypt(dense).real - ref).max() < 5e-3


def test_compiled_cheb_bitexact_relinsteps(relin_ctx, cheb_case):
    """exact=True: every CMULT lowers to a RelinStep (none stay eager)
    and the compiled run is bit-exact with the eager evaluation."""
    ctx = relin_ctx
    x, fn, coeffs = cheb_case
    ct = ctx.encrypt(x)

    tc = _trace_cheb(ctx.params, coeffs)
    comp = compile_program(tc)
    n_relin = sum(1 for s in comp.steps if isinstance(s, RelinStep))
    assert n_relin == comp.dfg.count(OpKind.CMULT)
    assert n_relin > 0

    assert_program_parity(
        ctx, comp, {"x": ct},
        lambda c, t: eval_chebyshev_bsgs(c, t, coeffs))


def test_compiled_cheb_multi_relin_fewer_moddowns(relin_ctx, cheb_case):
    """exact=False merges the giant-step product sums: MultiRelinSteps
    appear, total ModDowns drop at unchanged ModUps, reconciliation of
    predicted-vs-executed relin counts stays exact, and accuracy holds."""
    ctx = relin_ctx
    x, fn, coeffs = cheb_case
    ct = ctx.encrypt(x)
    c = ctx.counters

    tc = _trace_cheb(ctx.params, coeffs)
    comp = compile_program(tc)
    multi = compile_program(tc, exact=False)
    n_multi = sum(1 for s in multi.steps
                  if isinstance(s, MultiRelinStep))
    assert n_multi > 0 and multi.n_multi_relin == n_multi
    assert multi.summary()["merged_relins"] >= 2 * n_multi

    ex = ProgramExecutor(ctx)
    s0 = c.snapshot()
    exact_out = ex.run(comp, {"x": ct})["y"]
    d_exact = c.delta(s0)
    s1 = c.snapshot()
    res = ex.run(multi, {"x": ct}, with_report=True)
    d_multi = c.delta(s1)
    multi_out = res["y"]

    assert d_multi.moddown < d_exact.moddown
    assert d_multi.modup == d_exact.modup
    assert d_multi.relin == d_exact.relin
    rec = res.report.reconcile()
    assert rec["counts_match"], rec

    ref = fn(x)
    err_exact = np.abs(ctx.decrypt(exact_out).real - ref).max()
    err_multi = np.abs(ctx.decrypt(multi_out).real - ref).max()
    assert err_multi < err_exact * 1.5 + 1e-3


def test_compiled_cheb_batched(relin_ctx, cheb_case):
    """Batched execution drives relin_batched/multi_relin_sum_batched:
    bit-exact with the per-ct run, one jit trace per relin plan."""
    ctx = relin_ctx
    x, fn, coeffs = cheb_case
    rng = np.random.default_rng(12)
    nh = ctx.params.num_slots
    xs = [x, rng.uniform(-1, 1, nh)]
    cts = [ctx.encrypt(v) for v in xs]

    tc = _trace_cheb(ctx.params, coeffs)
    ex = ProgramExecutor(ctx)
    for comp in (compile_program(tc), compile_program(tc, exact=False)):
        before = dict(ctx.engine.trace_counts)
        outs = ex.run_batched(comp, {"x": cts})["y"]
        for ct, out_b in zip(cts, outs):
            out_1 = ex.run(comp, {"x": ct})["y"]
            assert _ct_equal(out_b, out_1)
        after = ctx.engine.trace_counts
        new_relin_traces = [
            k for k in after
            if k[0] in ("relin_b", "multi_relin_b")
            and after[k] != before.get(k)
        ]
        assert all(after[k] == 1 for k in new_relin_traces)


def test_multi_relin_pallas_parity():
    """Unbatched relin/multi_relin_sum run on BOTH backends: the pallas
    fused-IP accumulation is bit-exact with the jnp contraction."""
    p = CKKSParams(logN=8, L=3, alpha=2, k=2, q_bits=29, scale_bits=26)
    ctxs = {b: CKKSContext(p, seed=5, backend=b)
            for b in ("jnp", "pallas")}
    rng = np.random.default_rng(2)
    nh = p.num_slots
    xs = [rng.normal(size=nh) * 0.3 for _ in range(4)]
    outs = {}
    for b, ctx in ctxs.items():
        pairs = [(ctx.encrypt(xs[0]), ctx.encrypt(xs[1])),
                 (ctx.encrypt(xs[2]), ctx.encrypt(xs[3]))]
        lvl = pairs[0][0].level
        mods = ctx.pc.mods(ctx.chain(lvl))
        d0s, d1s, digs = [], [], []
        for a, bb in pairs:
            d0, d1, d2 = tensor_product(a, bb, mods, ctx.pc)
            d0s.append(d0)
            d1s.append(d1)
            digs.append(ctx.engine.modup(d2, lvl))
        outs[b] = (
            ctx.engine.multi_relin_sum(d0s, d1s, digs,
                                       ctx.keys.mult_key, lvl),
            ctx.engine.relin(d0s[0], d1s[0], None, ctx.keys.mult_key,
                             lvl, digits=digs[0]),
        )
    for got, exp in zip(outs["pallas"], outs["jnp"]):
        assert np.array_equal(np.asarray(got[0]), np.asarray(exp[0]))
        assert np.array_equal(np.asarray(got[1]), np.asarray(exp[1]))


# ------------------- batched pallas parity -------------------------------
# These replace the former pallas-vmap gate tests: the kernel suite is
# vmap-compatible (custom_vmap rules fold the batch into the kernel
# grids), so every *_batched engine entry runs on backend='pallas' and
# must be bit-exact with the jnp backend.

@pytest.fixture(scope="module")
def _pallas_pair():
    p = CKKSParams(logN=8, L=3, alpha=2, k=2, q_bits=29, scale_bits=26)
    return {b: CKKSContext(p, seed=5, backend=b)
            for b in ("jnp", "pallas")}


def _batched_tensor_square(ctx, msgs):
    import jax.numpy as jnp
    cts = [ctx.encrypt(m) for m in msgs]
    lvl = cts[0].level
    mods = ctx.pc.mods(ctx.chain(lvl))
    trips = [tensor_product(a, a, mods, ctx.pc) for a in cts]
    return tuple(jnp.stack([t[i] for t in trips]) for i in range(3)), lvl


def test_pallas_batched_rotation_parity(_pallas_pair):
    """Batched rotation (apply_galois_batched -> full keyswitch) on
    backend='pallas' is bit-exact with the jnp backend."""
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    nh = next(iter(_pallas_pair.values())).params.num_slots
    msgs = [rng.normal(size=nh) * 0.3 for _ in range(3)]
    outs = {}
    for b, ctx in _pallas_pair.items():
        cts = [ctx.encrypt(m) for m in msgs]
        c0b = jnp.stack([c.c0 for c in cts])
        c1b = jnp.stack([c.c1 for c in cts])
        g = ctx.pc.rns.galois_for_rotation(2)
        outs[b] = ctx.engine.apply_galois_batched(
            c0b, c1b, g, ctx.keys.rot_key(2), cts[0].level)
    for got, exp in zip(outs["pallas"], outs["jnp"]):
        assert np.array_equal(np.asarray(got), np.asarray(exp))


def test_pallas_batched_relin_parity(_pallas_pair):
    """Batched relin on backend='pallas' (fused-IP under vmap) is
    bit-exact with the jnp backend, with and without cached digits."""
    rng = np.random.default_rng(1)
    nh = next(iter(_pallas_pair.values())).params.num_slots
    msgs = [rng.normal(size=nh) * 0.3 for _ in range(2)]
    outs = {}
    for b, ctx in _pallas_pair.items():
        (d0, d1, d2), lvl = _batched_tensor_square(ctx, msgs)
        digs = ctx.engine.modup_batched(d2, lvl)
        outs[b] = (
            ctx.engine.relin_batched(d0, d1, d2, ctx.keys.mult_key, lvl),
            ctx.engine.relin_batched(d0, d1, None, ctx.keys.mult_key,
                                     lvl, digits=digs),
        )
    for got, exp in zip(outs["pallas"], outs["jnp"]):
        for g_arr, e_arr in zip(got, exp):
            assert np.array_equal(np.asarray(g_arr), np.asarray(e_arr))


def test_pallas_batched_multi_relin_parity(_pallas_pair):
    """Batched multi_relin_sum (one shared ModDown across products) on
    backend='pallas' is bit-exact with the jnp backend."""
    rng = np.random.default_rng(3)
    nh = next(iter(_pallas_pair.values())).params.num_slots
    msgs = [rng.normal(size=nh) * 0.3 for _ in range(2)]
    outs = {}
    for b, ctx in _pallas_pair.items():
        (d0, d1, d2), lvl = _batched_tensor_square(ctx, msgs)
        digs = ctx.engine.modup_batched(d2, lvl)
        outs[b] = ctx.engine.multi_relin_sum_batched(
            [d0, d0], [d1, d1], [digs, digs], ctx.keys.mult_key, lvl)
    for got, exp in zip(outs["pallas"], outs["jnp"]):
        assert np.array_equal(np.asarray(got), np.asarray(exp))
