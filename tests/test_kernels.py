"""Pallas kernel validation: interpret=True vs pure-jnp oracles vs the
exact uint64 core, swept over shapes and limb counts."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import poly
from repro.core.params import CKKSParams
from repro.kernels import modops
from repro.kernels.ntt.ops import (
    ntt_fwd, ntt_fwd_oracle, ntt_inv, ntt_inv_oracle, tables_for,
)
from repro.kernels.bconv.ops import bconv_kernel, bconv_oracle
from repro.kernels.fused_ip.ops import fused_ip_kernel, fused_ip_oracle


# ------------------------------ modops ----------------------------------

@pytest.mark.parametrize("q", [0x3FFFE001, 536608769, 268369921, 40961])
def test_mul32_split_and_mont(q):
    rng = np.random.default_rng(q)
    a = rng.integers(0, q, 4096, dtype=np.uint32)
    b = rng.integers(0, q, 4096, dtype=np.uint32)
    hi, lo = modops.mul32_split(jnp.asarray(a), jnp.asarray(b))
    full = a.astype(np.uint64) * b.astype(np.uint64)
    got = np.asarray(hi).astype(np.uint64) * (1 << 32) + np.asarray(lo)
    assert np.array_equal(got, full)
    if q % 2 == 1:
        qinv = modops.qinv_neg_host(q)
        b_m = modops.to_mont_host(b.astype(np.uint64), q)
        r = modops.mont_mul(
            jnp.asarray(a), jnp.asarray(b_m), jnp.uint32(q), jnp.uint32(qinv)
        )
        assert np.array_equal(np.asarray(r).astype(np.uint64), full % q)


def test_add_sub_mod():
    q = np.uint32(536608769)
    rng = np.random.default_rng(0)
    a = rng.integers(0, q, 1000, dtype=np.uint32)
    b = rng.integers(0, q, 1000, dtype=np.uint32)
    s = np.asarray(modops.add_mod(jnp.asarray(a), jnp.asarray(b), q))
    d = np.asarray(modops.sub_mod(jnp.asarray(a), jnp.asarray(b), q))
    assert np.array_equal(s.astype(np.uint64),
                          (a.astype(np.uint64) + b) % q)
    assert np.array_equal(d.astype(np.uint64),
                          (a.astype(np.uint64) + int(q) - b) % q)


# ------------------------------- NTT -------------------------------------

@pytest.mark.parametrize("logn,L", [(6, 1), (8, 3), (10, 2)])
def test_ntt_kernel_vs_oracle_roundtrip(logn, L):
    p = CKKSParams(logN=logn, L=L, alpha=1, k=1, q_bits=29)
    tabs = tables_for(p)
    primes = p.q_chain(L)
    rng = np.random.default_rng(logn)
    x = np.stack([rng.integers(0, q, p.N, dtype=np.uint32) for q in primes])
    xj = jnp.asarray(x)
    f_k = np.asarray(ntt_fwd(xj, primes, tabs))
    f_o = np.asarray(ntt_fwd_oracle(xj, primes, tabs))
    np.testing.assert_array_equal(f_k, f_o)
    i_k = np.asarray(ntt_inv(jnp.asarray(f_k), primes, tabs))
    i_o = np.asarray(ntt_inv_oracle(jnp.asarray(f_o), primes, tabs))
    np.testing.assert_array_equal(i_k, i_o)
    np.testing.assert_array_equal(i_k, x)


def test_ntt_kernel_consistent_with_core():
    """Kernel eval domain is a permutation of core's; negacyclic products
    agree exactly."""
    p = CKKSParams(logN=8, L=3, alpha=2, k=2, q_bits=29)
    tabs = tables_for(p)
    pc = poly.PolyContext(p)
    primes = p.q_chain(p.L)
    rng = np.random.default_rng(5)
    mods = np.array(primes, dtype=np.uint64)[:, None]
    x = np.stack([rng.integers(0, q, p.N, dtype=np.uint32) for q in primes])
    y = np.stack([rng.integers(0, q, p.N, dtype=np.uint32) for q in primes])
    fx = np.asarray(ntt_fwd(jnp.asarray(x), primes, tabs)).astype(np.uint64)
    fy = np.asarray(ntt_fwd(jnp.asarray(y), primes, tabs)).astype(np.uint64)
    prod_k = np.asarray(
        ntt_inv(jnp.asarray(((fx * fy) % mods).astype(np.uint32)), primes, tabs)
    ).astype(np.uint64)
    cfx = np.asarray(poly.ntt(jnp.asarray(x.astype(np.uint64)), primes, pc))
    cfy = np.asarray(poly.ntt(jnp.asarray(y.astype(np.uint64)), primes, pc))
    prod_c = np.asarray(
        poly.intt(jnp.asarray((cfx * cfy) % mods), primes, pc)
    )
    np.testing.assert_array_equal(prod_k, prod_c)
    for i in range(len(primes)):
        np.testing.assert_array_equal(
            np.sort(fx[i]), np.sort(cfx[i]), err_msg=f"limb {i} eval multiset"
        )


def test_core_ntt_routes_to_kernel(monkeypatch):
    """A pallas context running compiled kernels (a TPU) sends
    ``poly.ntt``/``poly.intt`` through the kernel: same residues as the
    uint64 stage loop.  Forced here on the CPU, kernel interpreted."""
    p = CKKSParams(logN=8, L=3, alpha=2, k=2, q_bits=29)
    pc = poly.PolyContext(p, backend="pallas")
    primes = p.q_chain(p.L)
    rng = np.random.default_rng(6)
    x = jnp.asarray(np.stack(
        [rng.integers(0, q, p.N, dtype=np.uint64) for q in primes]))
    exp_f = np.asarray(poly.ntt(x, primes, pc))
    exp_i = np.asarray(poly.intt(x, primes, pc))
    monkeypatch.setattr(pc, "kernel_ntt", True)
    np.testing.assert_array_equal(np.asarray(poly.ntt(x, primes, pc)), exp_f)
    np.testing.assert_array_equal(np.asarray(poly.intt(x, primes, pc)),
                                  exp_i)


def test_tpu_configuration_matches_cpu_end_to_end():
    """A pallas context configured as on a TPU (Montgomery ``%``, NTT
    through the kernel; interpreted here) gives the CPU configuration's
    residues through encrypt, rotate, multiply and rescale."""
    from repro.core.ckks import CKKSContext

    p = CKKSParams(logN=8, L=3, alpha=2, k=2, q_bits=29)
    z = np.random.default_rng(7).uniform(-1, 1, p.num_slots)
    outs = []
    for tpu in (False, True):
        ctx = CKKSContext(p, seed=5, backend="pallas")
        if tpu:
            ctx.pc.modq = poly._modq_mont
            ctx.pc.kernel_ntt = True
        ct = ctx.encrypt(z)
        ct = ctx.multiply(ctx.rotate(ct, 3), ct)
        outs.append(np.asarray(ct.c0))
        outs.append(np.asarray(ct.c1))
    np.testing.assert_array_equal(outs[0], outs[2])
    np.testing.assert_array_equal(outs[1], outs[3])


def test_modq_mont_matches_remainder():
    """The TPU reduction (no 64-bit division) equals ``%`` on the whole
    uint64 range, for the word sizes in use and a broadcast modulus."""
    rng = np.random.default_rng(11)
    qs = [1073479681, 536608769, 268369921, 40961]
    edge = [0, 1, 2**32 - 1, 2**32, 2**63, 2**64 - 1]
    for q in qs:
        x = np.concatenate([rng.integers(0, 2**64 - 1, 50000,
                                         dtype=np.uint64, endpoint=True),
                            np.array(edge + [q - 1, q, (q - 1) ** 2],
                                     dtype=np.uint64)])
        got = np.asarray(poly._modq_mont(jnp.asarray(x), np.uint64(q)))
        np.testing.assert_array_equal(got, x % np.uint64(q))
    qv = np.array(qs, dtype=np.uint64)[:, None]
    x = rng.integers(0, 2**62, (len(qs), 999), dtype=np.uint64)
    np.testing.assert_array_equal(
        np.asarray(poly._modq_mont(jnp.asarray(x), qv)),
        x % qv)


# ------------------------------ BConv ------------------------------------

@pytest.mark.parametrize("logn,ls,ld", [(6, 2, 2), (8, 3, 2), (8, 4, 4)])
def test_bconv_kernel_vs_oracle(logn, ls, ld):
    p = CKKSParams(logN=logn, L=max(ls - 1, 1), alpha=1, k=ld, q_bits=29)
    pc = poly.PolyContext(p)
    src = p.q_chain(ls - 1)
    dst = p.p_primes[:ld]
    rng = np.random.default_rng(logn + ls)
    x = np.stack([rng.integers(0, q, p.N, dtype=np.uint32) for q in src])
    xj = jnp.asarray(x)
    got = np.asarray(bconv_kernel(xj, src, dst, pc.rns))
    exp = np.asarray(bconv_oracle(xj, src, dst, pc.rns))
    np.testing.assert_array_equal(got, exp)


def test_bconv_kernel_vs_core():
    p = CKKSParams(logN=8, L=2, alpha=1, k=2, q_bits=29)
    pc = poly.PolyContext(p)
    src, dst = p.q_chain(2), p.p_primes
    rng = np.random.default_rng(9)
    x = np.stack([rng.integers(0, q, p.N, dtype=np.uint32) for q in src])
    got = np.asarray(
        bconv_kernel(jnp.asarray(x), src, dst, pc.rns)
    ).astype(np.uint64)
    core = np.asarray(
        poly.bconv(jnp.asarray(x.astype(np.uint64)), tuple(src), tuple(dst), pc)
    )
    np.testing.assert_array_equal(got, core)


def test_bconv_kernel_blocked():
    """Coefficient-blocked grid gives identical results (VMEM tiling).
    Blocks are whole (8, 128) tiles of lane rows: logN=11 holds two."""
    p = CKKSParams(logN=11, L=2, alpha=1, k=2, q_bits=29)
    pc = poly.PolyContext(p)
    src, dst = p.q_chain(2), p.p_primes
    rng = np.random.default_rng(10)
    x = jnp.asarray(
        np.stack([rng.integers(0, q, p.N, dtype=np.uint32) for q in src])
    )
    full = np.asarray(bconv_kernel(x, src, dst, pc.rns, block=0))
    blocked = np.asarray(bconv_kernel(x, src, dst, pc.rns, block=1024))
    np.testing.assert_array_equal(full, blocked)


# ----------------------------- fused IP ----------------------------------

@pytest.mark.parametrize("dnum,l,n,with_pt", [
    (2, 3, 256, False), (3, 5, 256, True), (4, 4, 1024, True),
])
def test_fused_ip_kernel_vs_oracle(dnum, l, n, with_pt):
    p = CKKSParams(logN=8, L=l - 1, alpha=1, k=1, q_bits=29)
    q = np.array(p.q_chain(l - 1), dtype=np.uint32)
    rng = np.random.default_rng(dnum * l)
    digits = np.stack(
        [np.stack([rng.integers(0, qq, n, dtype=np.uint32) for qq in q])
         for _ in range(dnum)]
    )
    evk = np.stack(
        [np.stack([np.stack([rng.integers(0, qq, n, dtype=np.uint32)
                             for qq in q]) for _ in range(2)])
         for _ in range(dnum)]
    )
    pt = (np.stack([rng.integers(0, qq, n, dtype=np.uint32) for qq in q])
          if with_pt else None)
    a0, a1 = fused_ip_kernel(digits, evk, pt, q)
    e0, e1 = fused_ip_oracle(digits, evk, pt, q)
    np.testing.assert_array_equal(np.asarray(a0), np.asarray(e0))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(e1))
