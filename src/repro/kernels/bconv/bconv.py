"""Pallas fast-basis-conversion kernel (the paper's tree-based BConvU).

Two passes, mirroring Fig. 12(b):

  scale  : t_i = [x_i * qhat_inv_i]_{q_i}           (grid over src limbs)
  reduce : y_j = sum_i t_i * (qhat_i mod d_j)  mod d_j  (grid over dst
           limbs x coefficient row blocks; the per-limb loop is the tree)

Limbs are lane-dense ``(R, 128)`` blocks (``modops.lane_layout``).  The
reduce pass keeps one row block of ALL source limbs in VMEM
(ls x rows x 128 x 4 B), which is the VMEM-resident working set the
paper's BConvU pipelines through its adder tree.  Constants are
Montgomery-form and live in SMEM with the moduli; data stays
normal-form (see kernels.modops).
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


def _scale_kernel(qinv_mont_ref, q_ref, qneg_ref, x_ref, o_ref, *, ls: int):
    i = pl.program_id(0) % np.int32(ls)
    o_ref[0] = mont_mul(x_ref[0], qinv_mont_ref[i], q_ref[i], qneg_ref[i])


def _reduce_kernel(c_ref, d_ref, dneg_ref, t_ref, o_ref, *, ls: int,
                   ld: int):
    j = pl.program_id(0) % np.int32(ld)
    d = d_ref[j]
    dn = dneg_ref[j]

    def tree(i, acc):                            # the BConvU adder tree
        return add_mod(acc, mont_mul(t_ref[i], c_ref[i, j], d, dn), d)

    o_ref[0] = jax.lax.fori_loop(
        1, ls, tree, mont_mul(t_ref[0], c_ref[0, j], d, dn))


def bconv_pallas(x, qhat_inv_mont, src_q, src_qneg, c_mont, dst_q, dst_qneg,
                 *, block: int = 0, interpret: bool = True):
    """x: (B*ls, R, lanes) uint32 coeff domain -> (B*ld, R, lanes) under
    the dst basis, batch-major rows (B inferred from the row count).

    qhat_inv_mont/src_q/src_qneg: (ls,); c_mont: (ls, ld) Montgomery of
    qhat_i mod d_j; dst_q/dst_qneg: (ld,) — all SMEM.  Batched rows read
    their limb's constants at ``program_id % ls`` / ``% ld``.  ``block``
    (coefficients, a multiple of 8 lane rows or the whole limb; 0 =
    whole) tiles the reduce pass over coefficient row blocks.
    """
    rows, r, lanes = x.shape
    ls = qhat_inv_mont.shape[0]
    ld = c_mont.shape[1]
    b = rows // ls
    rb = block // lanes if block else r
    assert r % rb == 0 and (rb % 8 == 0 or rb == r), (block, r, lanes)
    ld32 = np.int32(ld)

    t = pl.pallas_call(
        functools.partial(_scale_kernel, ls=ls),
        grid=(rows,),
        in_specs=[smem_spec(), smem_spec(), smem_spec(),
                  block_spec((1, r, lanes), lambda i: (i, 0, 0))],
        out_specs=block_spec((1, r, lanes), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, r, lanes), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(8 * r * lanes)),
        interpret=interpret,
    )(qhat_inv_mont, src_q, src_qneg, x)

    return pl.pallas_call(
        functools.partial(_reduce_kernel, ls=ls, ld=ld),
        grid=(b * ld, r // rb),
        in_specs=[
            smem_spec(2), smem_spec(), smem_spec(),
            block_spec((ls, rb, lanes), lambda j, c: (j // ld32, c, 0)),
        ],
        out_specs=block_spec((1, rb, lanes), lambda j, c: (j, c, 0)),
        out_shape=jax.ShapeDtypeStruct((b * ld, r, lanes), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(4 * (ls + 1) * rb * lanes)),
        interpret=interpret,
    )(c_mont, dst_q, dst_qneg, t)
