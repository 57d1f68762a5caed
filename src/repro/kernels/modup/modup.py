"""Fused Pallas ModUp kernel: INTT -> BConv reduce -> NTT, one call per digit.

The paper's xPU win is keeping ModUp's three phases on-chip; the op-by-op
backend instead round-trips every intermediate (INTT output, BConv scale,
BConv reduce) through HBM.  This kernel executes the whole digit in ONE
``pallas_call``:

  * grid = (B * ld,) walks the destination limbs (ld = extended-basis
    size), batch-major — limb ``s`` serves batch element ``s // ld``;
  * on each batch element's FIRST step (``s % ld == 0``) the digit's
    ``ls`` source limbs are INTT'd into a persistent VMEM scratch
    ``(ls, R, 128)``.  The BConv per-limb scale ``qhat_inv_i`` is FOLDED
    into the INTT post-twist table (one Montgomery multiply already
    applies ``psi^{-i} * n^{-1}``; composing ``* qhat_inv_i`` is free),
    so the BConvU scale pass disappears entirely;
  * every step then tree-reduces the scratch against one column of the
    Montgomery ``qhat_i mod d_j`` constants (SMEM) and runs the forward
    NTT of that single destination limb in place in the output block —
    reusing the NTT kernel's tiled passes (``fwd_ntt`` / ``inv_ntt``).
    Every source-limb and row-tile loop is a ``fori_loop``, so the body
    holds one INTT and one NTT whatever ``ls`` and N are.

No per-phase intermediate ever reaches HBM: the scratch persists across
sequential grid steps (TPU grids are sequential per core; interpret mode
matches).  VMEM residency at logN=16 (one limb = 256 KB, one packed
twiddle table = 268 KB): the double-buffered ``(ls, R, 128)`` input
block, the single-buffered source INTT tables (twists + twiddles), the
``(ls, R, 128)`` scratch, and two buffers each of the destination
tables and the output limb — ~17 MB at alpha = 12 (``PAPER_PARAMS``),
above v5e's 16 MiB default scoped VMEM, so the kernel requests its own
limit (``modops.vmem_limit``).

Domain bridging stays OUTSIDE the kernel (engine side): inputs are
bit-reversed eval order, outputs bit-reversed eval order, exactly like
``kernels/ntt``.  Data is normal-form uint32, constants Montgomery-form
(see ``kernels.modops``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.modops import (
    add_mod, block_spec, mont_mul, smem_spec, vmem_limit,
)
from repro.kernels.ntt.ntt import (
    TILE, ZERO, _loop, _tile_loop, fwd_ntt, inv_ntt,
)


def _modup_kernel(sq_ref, sqn_ref, c_ref, dq_ref, dqn_ref,
                  x_ref, tsc_ref, twi_ref, twf_ref, twtf_ref,
                  o_ref, t_ref, *, ls: int, ld: int, logn: int):
    j = pl.program_id(0) % np.int32(ld)

    @pl.when(j == 0)
    def _intt_sources():
        # Phase 1 (once per batch element): INTT every source limb into
        # the persistent scratch, post-twisted by psi^{-i} n^{-1} qhat_inv
        # — phases 1 and 2a of ModUp in one Montgomery pass each.
        _loop(ls, lambda i: inv_ntt(x_ref.at[i], t_ref.at[i], tsc_ref.at[i],
                                    twi_ref.at[i], sq_ref[i], sqn_ref[i],
                                    logn))

    # Phase 2b: adder-tree reduce into destination limb j, tile by tile.
    d = dq_ref[j]
    dn = dqn_ref[j]
    out = o_ref.at[ZERO]
    th = min(TILE, out.shape[0])

    def reduce_tile(r0):
        out[pl.ds(r0, th), :] = mont_mul(t_ref[0, pl.ds(r0, th), :],
                                         c_ref[0, j], d, dn)

        def tree(i):                             # the BConvU adder tree
            out[pl.ds(r0, th), :] = add_mod(
                out[pl.ds(r0, th), :],
                mont_mul(t_ref[i + 1, pl.ds(r0, th), :], c_ref[i + 1, j],
                         d, dn), d)

        _loop(ls - 1, tree)

    _tile_loop(out.shape[0], reduce_tile)
    # Phase 3: forward NTT of the new limb, in place in the output block.
    fwd_ntt(out, out, twf_ref.at[ZERO], twtf_ref.at[ZERO], d, dn, logn)


def modup_pallas(x, twist_i_scaled, tw_i, src_q, src_qneg,
                 c_mont, twist_f, tw_f, dst_q, dst_qneg,
                 *, logn: int, interpret: bool = True):
    """x: (B*ls, R, lanes) uint32 bit-reversed eval -> (B*ld, R, lanes)
    bit-reversed eval under the destination basis (B inferred from the
    row count).

    twist_i_scaled: (ls, R, lanes) and tw_i: (ls, R + AUX_ROWS, lanes)
    Montgomery INTT tables with the BConv scale folded into the
    post-twist; c_mont: (ls, ld) Montgomery ``qhat_i mod d_j``;
    twist_f/tw_f: (ld, ...) Montgomery NTT tables; src_q/src_qneg: (ls,);
    dst_q/dst_qneg: (ld,) — moduli and ``c_mont`` in SMEM.
    """
    ls, r, lanes = twist_i_scaled.shape
    rt = tw_i.shape[1]
    ld = tw_f.shape[0]
    assert r * lanes == 1 << logn
    b = x.shape[0] // ls
    ld32 = np.int32(ld)
    once = dict(pipeline_mode=pl.Buffered(1))     # constant-index tables
    limb_b = 4 * r * lanes
    vmem = (2 * ls * limb_b                       # x, double-buffered
            + ls * (limb_b + 4 * rt * lanes)      # source tables, once
            + ls * limb_b                         # INTT scratch
            + 2 * (2 * limb_b + 4 * rt * lanes))  # dst tables + out, x2
    kernel = functools.partial(_modup_kernel, ls=ls, ld=ld, logn=logn)
    return pl.pallas_call(
        kernel,
        name="modup",
        grid=(b * ld,),
        in_specs=[
            smem_spec(), smem_spec(), smem_spec(2), smem_spec(), smem_spec(),
            block_spec((ls, r, lanes), lambda s: (s // ld32, 0, 0)),
            block_spec((ls, r, lanes), lambda s: (0, 0, 0), **once),
            block_spec((ls, rt, lanes), lambda s: (0, 0, 0), **once),
            block_spec((1, r, lanes), lambda s: (s % ld32, 0, 0)),
            block_spec((1, rt, lanes), lambda s: (s % ld32, 0, 0)),
        ],
        out_specs=block_spec((1, r, lanes), lambda s: (s, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * ld, r, lanes), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((ls, r, lanes), jnp.uint32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(vmem)),
        interpret=interpret,
    )(src_q, src_qneg, c_mont, dst_q, dst_qneg,
      x, twist_i_scaled, tw_i, twist_f, tw_f)
