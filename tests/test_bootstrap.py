"""Bootstrap pipeline tests.

Precision note: with 30-bit RNS words (uint64 product bound) the bootstrap
scale is 2^29, so EvalMod precision is structurally ~2^-10 of q0 — real
deployments use 50-60-bit words.  Tolerances reflect that; the pipeline
structure (ModRaise -> 3-stage C2S -> EvalMod -> 3-stage S2C) is exactly
the paper's benchmark configuration [6].
"""
import numpy as np
import pytest

from repro.core.bootstrap import Bootstrapper
from repro.core.ckks import CKKSContext
from repro.core.params import CKKSParams


@pytest.fixture(scope="module")
def boot_ctx():
    p = CKKSParams(logN=10, L=23, alpha=3, k=4, q_bits=29, scale_bits=29,
                   q0_bits=30)
    return CKKSContext(p, seed=7, hamming_weight=8)


@pytest.fixture(scope="module")
def btp(boot_ctx):
    return Bootstrapper(boot_ctx, n_groups=3, mod_K=5, cheb_degree=59)


def test_stage_matrices_exact(btp, boot_ctx, rng):
    """Composed stage groups == special FFT (bit-reversal cancels)."""
    enc = boot_ctx.encoder
    nh = enc.Nh
    z = rng.normal(size=nh) + 1j * rng.normal(size=nh)
    comp = btp.c2s_groups[2] @ btp.c2s_groups[1] @ btp.c2s_groups[0]
    fsi = enc.fft_special_inv(z)
    assert np.abs(comp @ z - fsi[enc.bitrev]).max() < 1e-12
    comp_s = btp.s2c_groups[2] @ btp.s2c_groups[1] @ btp.s2c_groups[0]
    assert np.abs(comp_s @ (comp @ z) - z).max() < 1e-12


def test_stage_matrices_sparse(btp):
    """Each merged stage has few diagonals — the PKB structure HERO sees."""
    from repro.core.linear import matrix_diagonals

    for g in btp.c2s_groups + btp.s2c_groups:
        n_diags = len(matrix_diagonals(g))
        assert n_diags <= 2 ** 4 + 1, "merged stage should stay sparse"


def test_hom_c2s_s2c_identity(btp, boot_ctx, rng):
    ctx = boot_ctx
    nh = ctx.params.num_slots
    z = (rng.normal(size=nh) + 1j * rng.normal(size=nh)) * 0.01
    ct = ctx.encrypt(z)
    out = btp.slot_to_coeff(btp.coeff_to_slot(ct))
    assert np.abs(ctx.decrypt(out) - z).max() < 1e-3


@pytest.mark.slow
def test_full_bootstrap(btp, boot_ctx, rng):
    ctx = boot_ctx
    nh = ctx.params.num_slots
    z = (rng.normal(size=nh) + 1j * rng.normal(size=nh)) * 0.01
    ct0 = ctx.encrypt(z, level=0)
    out = btp.bootstrap(ct0)
    assert out.level >= 1, "bootstrap must recover usable levels"
    err = np.abs(ctx.decrypt(out) - z).max()
    assert err < 5e-3, f"bootstrap error {err}"


def test_mod_raise_exact(boot_ctx, rng):
    """ModRaise: decrypted coefficients == level-0 coefficients mod q0,
    with the q0-multiples (the I overflow) bounded by the sparse secret."""
    from repro.core import poly
    from repro.core.encoding import centered_crt

    ctx = boot_ctx
    nh = ctx.params.num_slots
    q0 = ctx.params.q_primes[0]
    z = (rng.normal(size=nh) + 1j * rng.normal(size=nh)) * 0.01
    ct0 = ctx.encrypt(z, level=0)
    btp_local = Bootstrapper.__new__(Bootstrapper)
    btp_local.ctx = ctx
    raised = Bootstrapper.mod_raise(btp_local, ct0)
    assert raised.level == ctx.params.L

    def raw_coeffs(ct):
        primes = ctx.chain(ct.level)
        mods = ctx.pc.mods(primes)
        m_eval = ctx.pc.add(
            ct.c0, ctx.pc.mul(ct.c1, ctx.keys.s_eval[: ct.level + 1], mods),
            mods,
        )
        return centered_crt(
            np.asarray(poly.intt(m_eval, primes, ctx.pc)), primes
        )

    low = raw_coeffs(ct0)
    high = raw_coeffs(raised)
    diff = high - low
    ks = diff / q0
    assert all(int(d) % q0 == 0 for d in diff), "m + q0*I structure broken"
    h = 8  # sparse secret hamming weight used by the fixture
    assert max(abs(int(k)) for k in ks) <= h + 1, "I overflow beyond bound"
