"""Pallas fused keyswitch inner-product kernel (xMU "MemOp fusion").

Computes, per extended-basis limb r (grid axis):

    acc_c[r] = sum_j digits[j, r, :] * evk[j, c, r, :]   (c = 0, 1)
    optionally followed by  acc_c[r] *= pt[r, :]          (fused PMul)

in ONE pass over VMEM-resident blocks — the paper's Fig. 10(d) fusion that
eliminates the row-switch write-back of the intermediate IP result between
sequential MemOps.  Limbs are lane-dense ``(R, 128)`` blocks
(``modops.lane_layout``); evk and pt are Montgomery-form, digits stay
normal; the per-limb modulus pair lives in SMEM.  Without a plaintext
the kernel takes no pt operand at all.
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


def _fused_ip_kernel(q_ref, qneg_ref, d_ref, k_ref, *rest, l: int,
                     dnum: int, with_pt: bool):
    pt_ref, o0_ref, o1_ref = rest if with_pt else (None,) + rest
    limb = pl.program_id(0) % np.int32(l)
    q = q_ref[limb]
    qn = qneg_ref[limb]
    acc0 = mont_mul(d_ref[0, 0], k_ref[0, 0, 0], q, qn)
    acc1 = mont_mul(d_ref[0, 0], k_ref[0, 1, 0], q, qn)
    for j in range(1, dnum):                     # trace-time unroll
        dj = d_ref[j, 0]
        acc0 = add_mod(acc0, mont_mul(dj, k_ref[j, 0, 0], q, qn), q)
        acc1 = add_mod(acc1, mont_mul(dj, k_ref[j, 1, 0], q, qn), q)
    if with_pt:
        pm = pt_ref[0]
        acc0 = mont_mul(acc0, pm, q, qn)
        acc1 = mont_mul(acc1, pm, q, qn)
    o0_ref[0] = acc0
    o1_ref[0] = acc1


def fused_ip_pallas(digits, evk_mont, pt_mont, q, qneg,
                    *, interpret: bool = True):
    """digits: (dnum, B*l, R, lanes) u32, batch-major rows; evk_mont:
    (dnum, 2, l, R, lanes) u32 Montgomery; pt_mont: (l, R, lanes) u32
    Montgomery or None; q/qneg: (l,) u32 (SMEM).  Returns (acc0, acc1),
    each (B*l, R, lanes) u32.

    B is inferred from the row count; batched rows read the (unbatched)
    evk/pt/modulus operands via ``% l`` index maps.
    """
    dnum, rows, r, lanes = digits.shape
    l = q.shape[0]
    l32 = np.int32(l)
    with_pt = pt_mont is not None
    limb = block_spec((1, r, lanes), lambda i: (i, 0, 0))
    in_specs = [
        smem_spec(), smem_spec(),
        block_spec((dnum, 1, r, lanes), lambda i: (0, i, 0, 0)),
        block_spec((dnum, 2, 1, r, lanes), lambda i: (0, 0, i % l32, 0, 0)),
    ]
    operands = [q, qneg, digits, evk_mont]
    if with_pt:
        in_specs.append(block_spec((1, r, lanes), lambda i: (i % l32, 0, 0)))
        operands.append(pt_mont)
    kernel = functools.partial(_fused_ip_kernel, l=l, dnum=dnum,
                               with_pt=with_pt)
    out = jax.ShapeDtypeStruct((rows, r, lanes), jnp.uint32)
    return pl.pallas_call(
        kernel,
        grid=(rows,),
        in_specs=in_specs,
        out_specs=[limb, limb],
        out_shape=[out, out],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(4 * r * lanes * (3 * dnum + 3))),
        interpret=interpret,
    )(*operands)
