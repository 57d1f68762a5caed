"""Vectorized RNS polynomial arithmetic on jnp uint64 arrays.

A polynomial under a basis of ``l`` primes is a ``(l, N)`` uint64 array of
residues.  Products of two residues (< 2^30) fit uint64 exactly, so plain
``(a * b) % q`` is exact.  Limb selections ("which primes") are static
Python tuples resolved to row indices at trace time — every distinct level
traces once, like a real FHE runtime specializing per level.

Domain convention: ciphertext polynomials live in EVAL (NTT) domain;
ModUp/ModDown run INTT -> BConv -> NTT per the paper's xPU pipeline.
"""
from __future__ import annotations

import copy
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.params import CKKSParams
from repro.core.rns import rns_context
from repro.kernels.modops import mont_mul, mont_redc


BACKENDS = ("jnp", "pallas")


# --------------------------- modular reduction --------------------------

def _modq_native(x, q):
    return x % q


def _mont_consts(q) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(q, -q^-1 mod 2^32, 2^64 mod q) as uint32 arrays of ``q``'s
    shape, computed on the host (moduli are host constants)."""
    q = np.asarray(q, dtype=np.uint64)
    flat = [int(v) for v in q.reshape(-1)]
    qn = [(-pow(v, -1, 1 << 32)) % (1 << 32) for v in flat]
    r2 = [pow(2, 64, v) for v in flat]
    return (q.astype(np.uint32),
            np.array(qn, dtype=np.uint32).reshape(q.shape),
            np.array(r2, dtype=np.uint32).reshape(q.shape))


def _mont_reduce(x, q32, qn, r2):
    """``x % q`` for uint64 ``x`` without 64-bit division, for odd
    2^10 <= q < 2^30: split x = hi * 2^32 + lo, reduce hi mod q by an
    f32 quotient estimate (exact to +-1 here, corrected), Montgomery
    REDC of (hi', lo) gives x * 2^-32 mod q, and a Montgomery product
    with r2 = 2^64 mod q undoes the 2^-32."""
    q64 = q32.astype(jnp.int64)
    h = (x >> 32).astype(jnp.uint32)
    k = jnp.floor(h.astype(jnp.float32) / q32.astype(jnp.float32))
    r = h.astype(jnp.int64) - k.astype(jnp.int64) * q64
    for _ in range(2):
        r = jnp.where(r < 0, r + q64, r)
        r = jnp.where(r >= q64, r - q64, r)
    t = mont_redc(r.astype(jnp.uint32), x.astype(jnp.uint32), q32, qn)
    return mont_mul(t, r2, q32, qn).astype(jnp.uint64)


# one compiled program per shape when called eagerly (op by op it would
# be ~150 tiny programs), inlined when traced inside a larger jit
_mont_reduce_jit = jax.jit(_mont_reduce)


def _modq_mont(x, q):
    """``x % q`` for uint64 ``x`` and host moduli ``q`` (broadcast)."""
    return _mont_reduce_jit(x, *_mont_consts(q))


def fence(x):
    """A kernel's output, bridged to the core's layout, kept out of the
    XLA fusions around it: fused into the uint64 glue, the bit-reversal
    gathers made a ModDown compile ~3x slower for a v5e (15.6 s -> 5.1
    s at logN=16, batch 4; compiles for a described chip)."""
    return jax.lax.optimization_barrier(x)


def default_platform() -> str:
    """Platform of the default device (``jax.default_device`` honoured)."""
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.default_backend()
    return getattr(dev, "platform", dev)


class PolyContext:
    """jnp-resident tables derived from RNSContext.

    ``backend`` selects the numeric implementation of the keyswitch hot
    path (see ``repro.core.keyswitch``): "jnp" runs batched uint64 jnp
    ops; "pallas" dispatches NTT/BConv/IP to the uint32 Montgomery
    Pallas kernels.  Both are bit-exact.

    Where the context computes is decided once, here: on the platform of
    the default device at construction.  On a TPU

      * ``modq`` (every device ``%`` of the core and the engine) is a
        32-bit Montgomery reduction: XLA has no 64-bit divider there and
        expands ``%`` into a bit-serial division — ~350 KB of HLO per op,
        seconds of compile each; one jitted jnp-backend keyswitch plan
        with ~200 of them took 11 minutes to compile for a v5e;
      * Pallas kernels are Mosaic-compiled (``interpret`` False), and a
        pallas context's ``ntt``/``intt`` run the NTT kernel: the uint64
        stage loop compiles for ~30-90 s per limb count (v5e, logN=16),
        the kernel for ~1 s, bit-exact with it.

    Elsewhere ``modq`` is the native ``%`` and kernels are interpreted.

    ``tables`` are the context's large device arrays (uint64 NTT tables;
    the Pallas kernels' tables, ``kernel_tables``, on the pallas
    backend).  A jitted function takes them as an argument and computes
    with ``bind(tables)``: compiled in as constants they made every
    keyswitch plan carry ~100-150 MB at logN=16.
    """

    TABLES = ("psi_pows", "psi_inv_pows", "bitrev", "stage_tw",
              "stage_tw_inv", "kernel_tables")

    def __init__(self, params: CKKSParams, backend: str = "jnp"):
        if backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
        self.params = params
        self.backend = backend
        self.rns = rns_context(params)
        r = self.rns
        self.moduli = r.moduli                         # (n_limbs,), host
        self.psi_pows = jnp.asarray(r.psi_pows)        # (n_limbs, N)
        self.psi_inv_pows = jnp.asarray(r.psi_inv_pows)
        self.n_inv = jnp.asarray(r.n_inv)
        self.bitrev = jnp.asarray(r.bitrev)
        self.stage_tw = [jnp.asarray(t) for t in r.stage_tw]
        self.stage_tw_inv = [jnp.asarray(t) for t in r.stage_tw_inv]

        self.platform = default_platform()
        on_tpu = self.platform == "tpu"
        self.modq = _modq_mont if on_tpu else _modq_native
        self.interpret = not on_tpu
        self.ntt_tables = self.kernel_tables = None
        if backend == "pallas":
            from repro.kernels.ntt.ops import tables_for

            self.ntt_tables = tables_for(params)
            self.kernel_tables = self.ntt_tables.device()
        self.kernel_ntt = backend == "pallas" and on_tpu

    @property
    def tables(self) -> dict:
        return {name: getattr(self, name) for name in self.TABLES}

    def bind(self, tables: dict) -> "PolyContext":
        """This context computing with ``tables`` (the traced argument
        of a jitted function) in place of its own."""
        pc = copy.copy(self)
        pc.__dict__.update(tables)
        return pc

    def limb_rows(self, primes: tuple[int, ...]) -> np.ndarray:
        return self.rns.limb_ids(primes)

    def mods(self, primes: tuple[int, ...]) -> jnp.ndarray:
        return self.moduli[self.limb_rows(primes)]

    # ------------------------- elementwise ops --------------------------
    def add(self, a, b, mods):
        return self.modq(a + b, mods[:, None])

    def sub(self, a, b, mods):
        return self.modq(a + mods[:, None] - b, mods[:, None])

    def mul(self, a, b, mods):
        return self.modq(a * b, mods[:, None])

    def neg(self, a, mods):
        return self.modq(mods[:, None] - a, mods[:, None])

    def mul_scalar(self, a, s, mods):
        """s: (l,) per-limb scalars already reduced."""
        return self.modq(a * s[:, None], mods[:, None])


# ------------------------------- NTT ------------------------------------

def ntt(x, primes: tuple[int, ...], pc: PolyContext):
    """Negacyclic forward NTT over stacked limbs. x: (l, N) uint64."""
    if pc.kernel_ntt:
        from repro.kernels.ntt.ops import ntt_fwd

        y = ntt_fwd(x.astype(jnp.uint32), primes, pc.ntt_tables,
                    interpret=pc.interpret, dev=pc.kernel_tables)
        return fence(y[:, pc.rns.bitrev].astype(jnp.uint64))
    modq = pc.modq
    rows = pc.limb_rows(primes)
    mods = pc.moduli[rows]
    l = len(primes)
    n = pc.params.N
    m1 = mods[:, None]
    x = modq(x * pc.psi_pows[rows], m1)
    x = x[:, pc.bitrev]
    m3 = mods[:, None, None]
    for s in range(pc.params.logN):
        m = 1 << s
        x = x.reshape(l, n // (2 * m), 2 * m)
        u = x[..., :m]
        tw = pc.stage_tw[s][rows][:, None, :]
        v = modq(x[..., m:] * tw, m3)
        x = jnp.concatenate([modq(u + v, m3), modq(u + m3 - v, m3)], axis=-1)
    return x.reshape(l, n)


def intt(x, primes: tuple[int, ...], pc: PolyContext):
    """Negacyclic inverse NTT."""
    if pc.kernel_ntt:
        from repro.kernels.ntt.ops import ntt_inv

        y = ntt_inv(x[:, pc.rns.bitrev].astype(jnp.uint32), primes,
                    pc.ntt_tables, interpret=pc.interpret,
                    dev=pc.kernel_tables)
        return fence(y.astype(jnp.uint64))
    modq = pc.modq
    rows = pc.limb_rows(primes)
    mods = pc.moduli[rows]
    l = len(primes)
    n = pc.params.N
    x = x[:, pc.bitrev]
    m3 = mods[:, None, None]
    for s in range(pc.params.logN):
        m = 1 << s
        x = x.reshape(l, n // (2 * m), 2 * m)
        u = x[..., :m]
        tw = pc.stage_tw_inv[s][rows][:, None, :]
        v = modq(x[..., m:] * tw, m3)
        x = jnp.concatenate([modq(u + v, m3), modq(u + m3 - v, m3)], axis=-1)
    x = x.reshape(l, n)
    m1 = mods[:, None]
    x = modq(x * pc.n_inv[rows][:, None], m1)
    return modq(x * pc.psi_inv_pows[rows], m1)


# --------------------------- basis conversion ---------------------------

def bconv(x, src: tuple[int, ...], dst: tuple[int, ...], pc: PolyContext,
          chunk: int = 8):
    """Fast basis conversion (coeff domain). x: (len(src), N) -> (len(dst), N).

    Approximate FBC — result may be off by a small multiple of prod(src);
    downstream ModDown/rescale absorbs it (standard RNS-CKKS).
    """
    qhat_inv, qhat_mod = pc.rns.bconv_consts(tuple(src), tuple(dst))
    src_mods = pc.mods(tuple(src))
    dst_mods = pc.mods(tuple(dst))
    modq = pc.modq
    t = modq(x * jnp.asarray(qhat_inv)[:, None], src_mods[:, None])
    qm = jnp.asarray(qhat_mod)                         # (ls, ld)
    dm = dst_mods[None, :, None]                       # (1, ld, 1)
    # Chunk over source limbs to bound the (ls, ld, N) intermediate.
    ls = len(src)
    acc = jnp.zeros((len(dst), x.shape[1]), dtype=jnp.uint64)
    for i in range(0, ls, chunk):
        part = modq(t[i : i + chunk, None, :] * qm[i : i + chunk, :, None], dm)
        acc = modq(acc + part.sum(axis=0), dst_mods[:, None])
    return acc


# --------------------------- ModUp / ModDown ----------------------------

@lru_cache(maxsize=None)
def _modup_perm(digit_primes: tuple[int, ...], new_primes: tuple[int, ...],
                target_primes: tuple[int, ...]) -> np.ndarray:
    """Row permutation assembling concat([digit, converted]) in target order."""
    pos = {p: i for i, p in enumerate(digit_primes + new_primes)}
    return np.array([pos[p] for p in target_primes], dtype=np.int64)


def modup_digit(x_digit, digit_primes, target_primes, pc: PolyContext,
                eval_domain: bool = True):
    """Lift one decomposition digit to the extended basis.

    x_digit: (alpha, N) residues under digit_primes (eval domain if
    eval_domain).  Returns (len(target), N) under ``target_primes``
    (superset containing digit_primes), eval domain.
    INTT -> BConv -> NTT for the new limbs; original limbs pass through.
    """
    coeff = intt(x_digit, digit_primes, pc) if eval_domain else x_digit
    new_primes = tuple(p for p in target_primes if p not in digit_primes)
    converted = bconv(coeff, tuple(digit_primes), new_primes, pc)
    if eval_domain:
        converted = ntt(converted, new_primes, pc)
    perm = _modup_perm(tuple(digit_primes), new_primes, tuple(target_primes))
    return jnp.concatenate([x_digit, converted])[perm]


def moddown(x, level: int, pc: PolyContext, eval_domain: bool = True):
    """Scale down by P: input under (Q_level u P), output under Q_level.

    x rows ordered: q_0..q_level, p_0..p_{k-1}.
    """
    params = pc.params
    q_primes = params.q_chain(level)
    p_primes = params.p_primes
    nq = len(q_primes)
    xq, xp = x[:nq], x[nq:]
    if eval_domain:
        xp_coeff = intt(xp, p_primes, pc)
    else:
        xp_coeff = xp
    conv = bconv(xp_coeff, tuple(p_primes), tuple(q_primes), pc)
    if eval_domain:
        conv = ntt(conv, tuple(q_primes), pc)
    q_mods = pc.mods(tuple(q_primes))
    diff = pc.sub(xq, conv, q_mods)
    pinv = jnp.asarray(pc.rns.p_inv_mod_q(level))
    return pc.mul_scalar(diff, pinv, q_mods)


def rescale(x, level: int, pc: PolyContext, eval_domain: bool = True):
    """Drop the last prime q_level: out_i = (x_i - x_last) / q_level mod q_i."""
    params = pc.params
    chain = params.q_chain(level)
    keep = chain[:-1]
    last = x[-1:]
    if eval_domain:
        last_coeff = intt(last, (chain[-1],), pc)
    else:
        last_coeff = last
    # Re-express x_last's residue under each remaining prime.
    lifted = bconv(last_coeff, (chain[-1],), tuple(keep), pc)
    if eval_domain:
        lifted = ntt(lifted, tuple(keep), pc)
    mods = pc.mods(tuple(keep))
    diff = pc.sub(x[:-1], lifted, mods)
    qinv = jnp.asarray(pc.rns.q_last_inv(level))
    return pc.mul_scalar(diff, qinv, mods)


# --------------------------- automorphism -------------------------------

def automorphism(x, primes: tuple[int, ...], galois: int, pc: PolyContext,
                 eval_domain: bool = True):
    """Apply X -> X^galois.  Functionally applied in coeff domain."""
    if eval_domain:
        x = intt(x, primes, pc)
    src, negmask = pc.rns.autom_tables(galois)
    mods = pc.mods(tuple(primes))[:, None]
    g = x[:, jnp.asarray(src)]
    negm = jnp.asarray(negmask)[None, :]
    g = jnp.where(negm == 1, pc.modq(mods - g, mods), g)
    if eval_domain:
        g = ntt(g, primes, pc)
    return g


def automorphism_eval(x, galois: int, pc: PolyContext):
    """Apply X -> X^galois directly in the eval domain: one gather.

    Bit-exact with ``automorphism(..., eval_domain=True)`` — the NTT's
    evaluation points are permuted by the Galois element (see
    ``RNSContext.autom_eval_perm``) — but with no INTT/NTT round trip.
    """
    return x[..., jnp.asarray(pc.rns.autom_eval_perm(galois))]
