"""uint32 Montgomery modular arithmetic from 16-bit limb partials.

Everything here is elementwise jnp on uint32 and runs identically inside a
Pallas TPU kernel body and as plain jnp.  Constraints:

  * modulus q odd, q < 2^30  (so the REDC accumulator fits uint32)
  * R = 2^32

Montgomery trick used throughout the kernels: keep VALUES in the normal
domain and constants (twiddles, BConv factors, evk) in Montgomery form —
mont_mul(value, const_mont) = value*const mod q, so no domain-conversion
passes are ever needed on the data.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

def mul32_split(a, b):
    """Full 32x32 -> (hi32, lo32) product via 16-bit limbs (no 64-bit ops).

    NOTE: literals stay Python ints so Pallas sees no captured constants.
    """
    a = a.astype(jnp.uint32)
    b = b.astype(jnp.uint32)
    a0, a1 = a & 0xFFFF, a >> 16
    b0, b1 = b & 0xFFFF, b >> 16
    ll = a0 * b0
    lh = a0 * b1
    hl = a1 * b0
    hh = a1 * b1
    mid = lh + hl                      # may wrap
    carry_mid = (mid < lh).astype(jnp.uint32)
    lo = ll + (mid << 16)              # may wrap
    carry_lo = (lo < ll).astype(jnp.uint32)
    hi = hh + (mid >> 16) + (carry_mid << 16) + carry_lo
    return hi, lo


def mont_redc(hi, lo, q, qinv_neg):
    """REDC: (hi*2^32 + lo) * 2^-32 mod q, for T < q*2^32, q < 2^30 odd.

    qinv_neg = -q^{-1} mod 2^32.
    """
    m = lo * qinv_neg                  # mod 2^32 (wrapping)
    mq_hi, _ = mul32_split(m, q)
    carry = (lo != 0).astype(jnp.uint32)
    t = hi + mq_hi + carry             # < 1.5*q, no overflow for q < 2^30
    return jnp.where(t >= q, t - q, t)


def mont_mul(a, b, q, qinv_neg):
    """a * b * 2^-32 mod q.  If b is in Montgomery form (b*2^32 mod q),
    the result is the plain product a*b mod q."""
    hi, lo = mul32_split(a, b)
    return mont_redc(hi, lo, q, qinv_neg)


def add_mod(a, b, q):
    s = a + b                          # < 2q < 2^31, no overflow
    return jnp.where(s >= q, s - q, s)


def sub_mod(a, b, q):
    return jnp.where(a >= b, a - b, a + q - b)


# ------------------------------ TPU layout ------------------------------

LANES = 128          # vreg lane count: the minor dim of every kernel block


def lane_layout(n: int) -> tuple[int, int]:
    """(rows, lanes) view of one N-coefficient limb.

    Kernels see a limb as a lane-dense ``(N/128, 128)`` block so that the
    last two block dims are (8, 128)-tileable (or whole, for N < 1024) —
    a ``(1, N)`` row block is refused by the TPU lowering.  Rings below
    128 coefficients fall back to one ``(1, N)`` row (whole dims)."""
    lanes = min(n, LANES)
    return n // lanes, lanes


def block_spec(shape, index_map, **kw):
    """``pl.BlockSpec`` whose index map yields int32 block indices.

    The package runs with ``jax_enable_x64`` on (``repro.core``), under
    which a Python-int block index lowers as i64, which Mosaic refuses.
    Index maps built here may return Python ints; arithmetic on the grid
    indices must use int32 operands (``np.int32(l)``, not ``l``)."""
    from jax.experimental import pallas as pl

    def imap(*grid):
        return tuple(np.int32(v) if isinstance(v, int) else v
                     for v in index_map(*grid))

    return pl.BlockSpec(shape, imap, **kw)


def smem_spec(ndim: int = 1):
    """Whole-array SMEM operand (per-limb scalars, indexed in-kernel by
    ``program_id``), with an int32 index map (see ``block_spec``)."""
    from jax.experimental.pallas import tpu as pltpu

    return block_spec(None, lambda *grid: (0,) * ndim,
                      memory_space=pltpu.SMEM)


def vmem_limit(block_bytes: int) -> int:
    """Scoped-VMEM request for a kernel whose pipelined blocks and
    scratch total ``block_bytes``: double the blocks plus 16 MiB for
    body temporaries, capped well below v5e's 128 MiB of VMEM."""
    return int(min(2 * block_bytes + (16 << 20), 100 << 20))


# ----------------------- host-side constant helpers ----------------------

def default_interpret() -> bool:
    """Pallas interpret mode unless a real TPU backend is attached."""
    import jax

    return jax.default_backend() != "tpu"


def qinv_neg_host(q: int) -> np.uint32:
    """-q^{-1} mod 2^32 (host precompute)."""
    return np.uint32((-pow(q, -1, 1 << 32)) % (1 << 32))


def to_mont_host(x: np.ndarray, q: int) -> np.ndarray:
    """Convert constants to Montgomery form on the host: exact in uint64
    for residues x < q < 2^31 (``x << 32`` < 2^63)."""
    x = np.asarray(x).astype(np.uint64)
    return ((x << np.uint64(32)) % np.uint64(q)).astype(np.uint32)
