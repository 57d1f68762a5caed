"""RNS precomputed tables: NTT twiddles, basis-conversion constants,
automorphism permutations.

Tables are built once per parameter set with exact Python integers and
stored as numpy uint64 arrays; ``repro.core.poly`` lifts them to jnp.
"""
from __future__ import annotations

from functools import lru_cache

import numpy as np

from repro.core import nt
from repro.core.params import CKKSParams


class PrimeTables:
    """Per-prime negacyclic NTT tables (DIT, bit-reversed input)."""

    def __init__(self, p: int, logn: int):
        self.p = p
        self.logn = logn
        n = 1 << logn
        self.n = n
        psi = nt.root_of_unity(2 * n, p)       # 2n-th root: negacyclic twist
        omega = psi * psi % p                  # n-th root for the cyclic NTT
        self.psi = psi
        self.omega = omega
        self.n_inv = nt.modinv(n, p)

        self.psi_pows = _pow_table(psi, n, p)
        psi_inv = nt.modinv(psi, p)
        self.psi_inv_pows = _pow_table(psi_inv, n, p)
        self.bitrev = np.array(nt.bit_reverse_indices(n), dtype=np.int64)

        omega_inv = nt.modinv(omega, p)
        # Stage s (s = 0..logn-1) has 2^s twiddles w^(n >> (s+1) * j).
        self.stage_tw = [
            _pow_table(pow(omega, n >> (s + 1), p), 1 << s, p)
            for s in range(logn)
        ]
        self.stage_tw_inv = [
            _pow_table(pow(omega_inv, n >> (s + 1), p), 1 << s, p)
            for s in range(logn)
        ]


def _pow_table(base: int, count: int, p: int) -> np.ndarray:
    """[base^i mod p for i < count] as uint64, exact.

    Two-level table: base^(a*B + b) = (base^B)^a * base^b, with B ~
    sqrt(count) Python pows per level and one vectorized uint64 outer
    product (p < 2^31, so every product fits)."""
    width = 1 << ((count.bit_length() + 1) // 2)
    lo = np.array([pow(base, b, p) for b in range(width)], dtype=np.uint64)
    step = pow(base, width, p)
    hi = np.array([pow(step, a, p) for a in range(-(-count // width))],
                  dtype=np.uint64)
    return ((hi[:, None] * lo[None, :]) % np.uint64(p)).reshape(-1)[:count]


def ntt_ref(a: np.ndarray, t: PrimeTables) -> np.ndarray:
    """Reference negacyclic forward NTT (numpy uint64, exact)."""
    p = np.uint64(t.p)
    x = (a.astype(np.uint64) * t.psi_pows) % p
    x = x[t.bitrev]
    n = t.n
    for s in range(t.logn):
        m = 1 << s
        x = x.reshape(n // (2 * m), 2 * m)
        u = x[:, :m]
        v = (x[:, m:] * t.stage_tw[s][None, :]) % p
        x = np.concatenate([(u + v) % p, (u + p - v) % p], axis=1)
    return x.reshape(n)


def intt_ref(a: np.ndarray, t: PrimeTables) -> np.ndarray:
    """Reference negacyclic inverse NTT."""
    p = np.uint64(t.p)
    x = a.astype(np.uint64)[t.bitrev]
    n = t.n
    for s in range(t.logn):
        m = 1 << s
        x = x.reshape(n // (2 * m), 2 * m)
        u = x[:, :m]
        v = (x[:, m:] * t.stage_tw_inv[s][None, :]) % p
        x = np.concatenate([(u + v) % p, (u + p - v) % p], axis=1)
    x = x.reshape(n)
    x = (x * np.uint64(t.n_inv)) % p
    return (x * t.psi_inv_pows) % p


@lru_cache(maxsize=8)
def rns_context(params: CKKSParams) -> "RNSContext":
    """The (read-only) tables of ``params``, built once per process and
    shared by every ``PolyContext`` and kernel table set."""
    return RNSContext(params)


class RNSContext:
    """All tables for a CKKSParams instance, stacked per-limb for jnp use."""

    def __init__(self, params: CKKSParams):
        self.params = params
        self.all_primes: tuple[int, ...] = params.q_primes + params.p_primes
        self.prime_index = {p: i for i, p in enumerate(self.all_primes)}
        self.tables = [PrimeTables(p, params.logN) for p in self.all_primes]
        self.moduli = np.array(self.all_primes, dtype=np.uint64)

        logn, n = params.logN, params.N
        n_limbs = len(self.all_primes)
        self.psi_pows = np.stack([t.psi_pows for t in self.tables])
        self.psi_inv_pows = np.stack([t.psi_inv_pows for t in self.tables])
        self.n_inv = np.array([t.n_inv for t in self.tables], dtype=np.uint64)
        self.bitrev = self.tables[0].bitrev  # same for all primes
        # stage_tw[s]: (n_limbs, 2^s)
        self.stage_tw = [
            np.stack([t.stage_tw[s] for t in self.tables]) for s in range(logn)
        ]
        self.stage_tw_inv = [
            np.stack([t.stage_tw_inv[s] for t in self.tables])
            for s in range(logn)
        ]
        assert self.psi_pows.shape == (n_limbs, n)

    def limb_ids(self, primes: tuple[int, ...]) -> np.ndarray:
        return np.array([self.prime_index[p] for p in primes], dtype=np.int64)

    # ---------------- basis conversion constants ----------------------
    @lru_cache(maxsize=None)
    def bconv_consts(
        self, src: tuple[int, ...], dst: tuple[int, ...]
    ) -> tuple[np.ndarray, np.ndarray]:
        """Fast-basis-conversion constants src -> dst.

        Returns (qhat_inv_mod_src[i], qhat_mod_dst[i, j]) with
        qhat_i = prod(src)/src_i.  FBC: y_j = sum_i [x_i * qhat_inv_i]_{s_i}
        * (qhat_i mod d_j) mod d_j (approximate: off by a small multiple of
        prod(src), absorbed by ModDown rounding / scheme noise).
        """
        prod = 1
        for s in src:
            prod *= s
        qhat_inv = np.array(
            [nt.modinv(prod // s, s) for s in src], dtype=np.uint64
        )
        qhat_mod = np.array(
            [[(prod // s) % d for d in dst] for s in src], dtype=np.uint64
        )
        return qhat_inv, qhat_mod

    @lru_cache(maxsize=None)
    def p_inv_mod_q(self, level: int) -> np.ndarray:
        """P^{-1} mod q_i for ModDown at ``level``."""
        P = self.params.P
        return np.array(
            [nt.modinv(P, q) for q in self.params.q_chain(level)],
            dtype=np.uint64,
        )

    @lru_cache(maxsize=None)
    def q_last_inv(self, level: int) -> np.ndarray:
        """q_level^{-1} mod q_i (i < level) for rescale."""
        chain = self.params.q_chain(level)
        q_last = chain[-1]
        return np.array(
            [nt.modinv(q_last, q) for q in chain[:-1]], dtype=np.uint64
        )

    # ---------------- automorphism tables ------------------------------
    @lru_cache(maxsize=None)
    def autom_tables(self, galois: int) -> tuple[np.ndarray, np.ndarray]:
        """Gather indices + sign for b(X) = a(X^galois) in coeff domain.

        b[j] = sign[j] * a[src[j]]  (sign encoded as 0 -> +, 1 -> negate).
        """
        n = self.params.N
        two_n = 2 * n
        kinv = nt.modinv(galois, two_n)
        j = np.arange(n, dtype=np.int64)
        i0 = (j * kinv) % two_n
        src = i0 % n
        neg = (i0 >= n).astype(np.uint64)
        return src, neg

    @lru_cache(maxsize=None)
    def autom_eval_perm(self, galois: int) -> np.ndarray:
        """Eval-domain automorphism as a pure permutation (no signs).

        The negacyclic NTT evaluates at psi^(2j+1) (natural order), so
        a(X^g) at point j is a's value at the point with odd exponent
        g*(2j+1) mod 2N:  out[j] = in[perm[j]].  This is how real FHE
        libraries apply Galois in the NTT domain — one gather, exactly
        equal to the coeff-domain INTT -> permute -> NTT round trip.
        """
        n = self.params.N
        two_n = 2 * n
        j = np.arange(n, dtype=np.int64)
        return ((galois * (2 * j + 1)) % two_n - 1) // 2

    def galois_for_rotation(self, steps: int) -> int:
        """Galois element 5^steps mod 2N rotating slots left by ``steps``."""
        two_n = 2 * self.params.N
        return pow(5, steps % self.params.num_slots, two_n)

    GALOIS_CONJ = -1  # sentinel; conjugation uses element 2N-1

    def galois_conjugate(self) -> int:
        return 2 * self.params.N - 1
