"""Pallas negacyclic NTT kernel (radix-2, trace-time-unrolled stages).

TPU adaptation of the paper's iterative NTTU (Fig. 12(a)):

  * DIF (forward, natural -> bit-reversed) and DIT (inverse, bit-reversed
    -> natural) so NO in-kernel permutation/gather is ever needed — the
    eval domain simply lives in bit-reversed order, which all elementwise
    consumers (IP/PMul/CAdd) are indifferent to.
  * One RNS limb's full polynomial is VMEM-resident per grid step, as a
    lane-dense ``(R, 128)`` block (``modops.lane_layout``; N=2^16 ->
    (512, 128), 256 KB << VMEM); coefficient ``i`` sits at row
    ``i // 128``, lane ``i % 128``.  The grid walks limbs, which is also
    the paper's per-limb NTTU parallelism axis.
  * A butterfly of span ``m`` pairs coefficients ``i`` and ``i ^ m``.
    The limb lives in a VMEM ref and every pass walks it one vreg tile
    (8 rows) at a time in a ``fori_loop``, so the kernel body stays a
    few hundred vector ops whatever N is (Mosaic compile time follows
    body size).  Spans of 8 rows or more pair whole tiles (rows r0 and
    r0 + mr), one pass per stage; every shorter span (1, 2, 4 rows, and
    all spans inside a row) stays inside a tile, so all of them run in
    ONE pass per tile, the partner fetched by two ``pltpu.roll``s along
    the sublane or lane axis and selected by an iota mask — no value
    leaves the (8, 128) tiling, so Mosaic needs no relayout.
  * uint32 Montgomery arithmetic (see kernels.modops): data stays in the
    normal domain, twiddles/twists are pre-converted to Montgomery form;
    the per-limb modulus and its Montgomery inverse live in SMEM.

Twiddle table (``pack_twiddles``), ``(R + AUX_ROWS, 128)`` per limb:
rows ``[0, R)`` are the flat twiddle tree ``tw[m + j] = w^{(N >> (s+1))
* j}`` (m = 2^s) viewed as ``(R, 128)`` — stage ``m = 128 * mr`` with
``mr >= 8`` reads rows ``[mr, 2 mr)``.  The ``AUX_ROWS`` rows after it
hold the stages whose twiddles are not a whole-tile row slice, already
broadcast to the positions the masked butterfly multiplies: row ``s``
(``m = 2^s < 128``) is ``tw[m + (lane % m)]``, row 7 is tree row 1
(``mr = 1``), and rows ``8..15`` / ``16..23`` tile tree rows ``[2, 4)``
/ ``[4, 8)`` down 8 sublanes (``mr = 2`` / ``4``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.modops import (
    add_mod, block_spec, lane_layout, mont_mul, smem_spec, sub_mod,
    vmem_limit,
)

AUX_ROWS = 24
TILE = 8             # sublanes per vreg: the row tile of every pass
ZERO = np.int32(0)   # ref.at[] index: a Python 0 would lower as i64


def pack_twiddles(tree: np.ndarray) -> np.ndarray:
    """Flat (N,) twiddle tree -> the kernel's (R + AUX_ROWS, lanes) table
    (layout in the module docstring; host-side, any dtype)."""
    n = tree.shape[-1]
    rows, lanes = lane_layout(n)
    t2 = tree.reshape(rows, lanes)
    aux = np.ones((AUX_ROWS, lanes), dtype=tree.dtype)
    lane = np.arange(lanes)
    for s in range(lanes.bit_length() - 1):
        m = 1 << s
        aux[s] = tree[m + lane % m]
    if rows > 1:
        aux[7] = t2[1]
    for blk, mr in ((1, 2), (2, 4)):
        if mr < rows:
            aux[8 * blk : 8 * blk + 8] = t2[mr + np.arange(8) % mr]
    return np.concatenate([t2, aux])


def _partner(x, k: int, axis: int):
    """(x[i ^ k], i & k == 0) along ``axis`` — the butterfly partner of
    every position and the mask of the upper ("u") half.

    Two rolls give x[i + k] and x[i - k]; which roll is which is read off
    an equally-rolled iota, so the result does not depend on the roll
    direction convention of the lowering."""
    n = x.shape[axis]
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    k32, back = np.int32(k), np.int32(n - k)    # i32 shifts under x64
    a = pltpu.roll(x, k32, axis)
    b = pltpu.roll(x, back, axis)
    a_src = pltpu.roll(idx, k32, axis)
    return jnp.where(a_src == (idx ^ k), a, b), (idx & k) == 0


def _loop(n: int, body) -> None:
    """``body(i)`` for ``i`` in ``range(n)``, as a loop with an int32
    index (a ``fori_loop`` over Python-int bounds counts in int64 while
    x64 is on, and Mosaic refuses i64 index arithmetic)."""
    def step(i):
        body(i)
        return i + np.int32(1)

    jax.lax.while_loop(lambda i: i < np.int32(n), step, np.int32(0))


def _tile_loop(rows: int, body) -> None:
    """``body(r0)`` for every row tile start ``r0`` of a (rows, lanes)
    limb: one vreg (8 rows) per step, or the whole limb below 8 rows."""
    th = min(TILE, rows)
    _loop(rows // th, lambda t: body(pl.multiple_of(t * np.int32(th), th)))


def _small_twiddle(tw, s: int, rows: int):
    """Twiddles of a within-tile stage, already laid out per position:
    a (1, lanes) row, or one (th, lanes) tile for mr = 2 | 4."""
    lanes = tw.shape[1]
    m = 1 << s
    if m < lanes:
        return tw[rows + s : rows + s + 1, :]
    mr = m // lanes
    if mr == 1:
        return tw[rows + 7 : rows + 8, :]
    base = rows + 8 * (mr // 2)                            # mr = 2 | 4
    return tw[base : base + min(TILE, rows), :]


def _small_stage(x, s: int, w, q, qinv, inverse: bool):
    """A stage whose butterflies stay inside one row tile: the partner
    comes from two rolls (lanes, or sublanes for mr = 1, 2, 4)."""
    lanes = x.shape[1]
    m = 1 << s
    if m >= lanes:
        p, upper = _partner(x, m // lanes, 0)
    else:
        p, upper = _partner(x, m, 1)
    u = jnp.where(upper, x, p)
    v = jnp.where(upper, p, x)
    if inverse:
        vw = mont_mul(v, w, q, qinv)
        return jnp.where(upper, add_mod(u, vw, q), sub_mod(u, vw, q))
    return jnp.where(upper, add_mod(u, v, q),
                     mont_mul(sub_mod(u, v, q), w, q, qinv))


def _small_pass(src, dst, stages, tw, q, qinv, inverse: bool,
                twist=None, twist_first: bool = False):
    """All within-tile ``stages`` (in order) tile by tile, src -> dst,
    with an optional Montgomery twist before or after them."""
    rows = dst.shape[0]
    th = min(TILE, rows)
    ws = [_small_twiddle(tw, s, rows) for s in stages]     # loop-invariant

    def body(r0):
        x = src[pl.ds(r0, th), :]
        if twist is not None and twist_first:
            x = mont_mul(x, twist[pl.ds(r0, th), :], q, qinv)
        for s, w in zip(stages, ws):
            x = _small_stage(x, s, w, q, qinv, inverse)
        if twist is not None and not twist_first:
            x = mont_mul(x, twist[pl.ds(r0, th), :], q, qinv)
        dst[pl.ds(r0, th), :] = x

    _tile_loop(rows, body)


def _big_stage(x, s: int, tw, q, qinv, inverse: bool) -> None:
    """A stage of span >= 8 rows, in place: tile pairs (u, v) = rows
    [r0, r0 + 8) and [r0 + mr, r0 + mr + 8), twiddles from tree rows
    [mr + j*8, mr + j*8 + 8)."""
    rows, lanes = x.shape
    mr = (1 << s) // lanes
    per = mr // TILE                            # tiles per half block

    def body(it):
        b = jax.lax.div(it, np.int32(per))
        j = jax.lax.rem(it, np.int32(per))
        r0 = pl.multiple_of(b * np.int32(2 * mr) + j * np.int32(TILE), TILE)
        wr = pl.multiple_of(j * np.int32(TILE) + np.int32(mr), TILE)
        w = tw[pl.ds(wr, TILE), :]
        u = x[pl.ds(r0, TILE), :]
        v = x[pl.ds(r0 + mr, TILE), :]
        if inverse:
            vw = mont_mul(v, w, q, qinv)
            lo, hi = add_mod(u, vw, q), sub_mod(u, vw, q)
        else:
            lo, hi = add_mod(u, v, q), mont_mul(sub_mod(u, v, q), w, q, qinv)
        x[pl.ds(r0, TILE), :] = lo
        x[pl.ds(r0 + mr, TILE), :] = hi

    _loop(rows // (2 * TILE), body)


def _split(lanes: int, order):
    """Stages in ``order`` split into (within-tile, >= 8-row) spans."""
    small = [s for s in order if (1 << s) < TILE * lanes]
    big = [s for s in order if (1 << s) >= TILE * lanes]
    return small, big


def fwd_ntt(src, dst, twist, tw, q, qinv, logn: int) -> None:
    """Forward NTT of one (rows, lanes) limb, ``src`` ref -> ``dst`` ref
    (may alias).  ``twist``: (rows, lanes) Montgomery psi^i; ``tw``: the
    limb's packed Montgomery twiddle table (``pack_twiddles``)."""
    small, big = _split(dst.shape[1], range(logn - 1, -1, -1))
    if big:                                  # DIF: big blocks first
        def pre(r0):
            dst[pl.ds(r0, TILE), :] = mont_mul(
                src[pl.ds(r0, TILE), :], twist[pl.ds(r0, TILE), :], q, qinv)

        _tile_loop(dst.shape[0], pre)
        for s in big:
            _big_stage(dst, s, tw, q, qinv, inverse=False)
        _small_pass(dst, dst, small, tw, q, qinv, inverse=False)
    else:
        _small_pass(src, dst, small, tw, q, qinv, inverse=False,
                    twist=twist, twist_first=True)


def inv_ntt(src, dst, twist, tw, q, qinv, logn: int) -> None:
    """Inverse NTT (bit-reversed eval -> natural coeff), ``src`` ->
    ``dst``; ``twist`` folds psi^-i * n^-1 (and any per-limb scale)."""
    small, big = _split(dst.shape[1], range(logn))
    if big:                                  # DIT: small blocks first
        _small_pass(src, dst, small, tw, q, qinv, inverse=True)
        for s in big:
            _big_stage(dst, s, tw, q, qinv, inverse=True)

        def post(r0):
            dst[pl.ds(r0, TILE), :] = mont_mul(
                dst[pl.ds(r0, TILE), :], twist[pl.ds(r0, TILE), :], q, qinv)

        _tile_loop(dst.shape[0], post)
    else:
        _small_pass(src, dst, small, tw, q, qinv, inverse=True,
                    twist=twist)


def _ntt_kernel(q_ref, qinv_ref, x_ref, twist_ref, tw_ref, o_ref,
                *, l: int, logn: int, inverse: bool):
    limb = pl.program_id(0) % np.int32(l)
    run = inv_ntt if inverse else fwd_ntt
    run(x_ref.at[ZERO], o_ref.at[ZERO], twist_ref.at[ZERO], tw_ref.at[ZERO],
        q_ref[limb], qinv_ref[limb], logn)


def ntt_pallas(x, twist, tw, q, qinv, *, logn: int, inverse: bool,
               interpret: bool = True):
    """x: (B*l, R, lanes) uint32, batch-major rows; twist: (l, R, lanes)
    and tw: (l, R + AUX_ROWS, lanes) uint32 Montgomery; q/qinv: (l,)
    uint32 (SMEM).  B is inferred from the row count.

    Grid walks all B*l limbs; each program transforms one polynomial in
    VMEM, reading its limb's tables via a ``% l`` index map — batching
    costs no table replication.
    """
    rows, r, lanes = x.shape
    l = twist.shape[0]
    assert r * lanes == 1 << logn
    rt = tw.shape[1]
    l32 = np.int32(l)
    kernel = functools.partial(_ntt_kernel, l=l, logn=logn, inverse=inverse)
    return pl.pallas_call(
        kernel,
        grid=(rows,),
        in_specs=[
            smem_spec(), smem_spec(),
            block_spec((1, r, lanes), lambda i: (i, 0, 0)),
            block_spec((1, r, lanes), lambda i: (i % l32, 0, 0)),
            block_spec((1, rt, lanes), lambda i: (i % l32, 0, 0)),
        ],
        out_specs=block_spec((1, r, lanes), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, r, lanes), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(4 * (3 * r + rt) * lanes)),
        interpret=interpret,
    )(q, qinv, x, twist, tw)
