"""jit'd wrapper + constants for the BConv kernel."""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.custom_batching import custom_vmap

from repro.core.rns import RNSContext
from repro.kernels.bconv.bconv import bconv_pallas
from repro.kernels.bconv import ref as _ref
from repro.kernels.modops import (
    default_interpret, lane_layout, qinv_neg_host, to_mont_host,
)


class BConvKernelConsts:
    def __init__(self, rns: RNSContext, src: tuple[int, ...],
                 dst: tuple[int, ...]):
        qhat_inv, qhat_mod = rns.bconv_consts(tuple(src), tuple(dst))
        ls, ld = len(src), len(dst)
        self.qhat_inv = qhat_inv
        self.qhat_mod = qhat_mod
        self.src_q = np.array(src, dtype=np.uint32)
        self.dst_q = np.array(dst, dtype=np.uint32)
        self.src_qneg = np.array(
            [qinv_neg_host(q) for q in src], dtype=np.uint32
        )
        self.dst_qneg = np.array(
            [qinv_neg_host(q) for q in dst], dtype=np.uint32
        )
        self.qhat_inv_mont = np.concatenate(
            [to_mont_host(np.array([qhat_inv[i]]), src[i]) for i in range(ls)]
        )
        self.qhat_mod_mont = np.stack(
            [
                np.array(
                    [int(to_mont_host(np.array([qhat_mod[i, j]]), dst[j])[0])
                     for j in range(ld)],
                    dtype=np.uint32,
                )
                for i in range(ls)
            ]
        )


@lru_cache(maxsize=None)
def _consts(rns_id, src, dst):
    rns = _RNS_REGISTRY[rns_id]
    return BConvKernelConsts(rns, src, dst)


_RNS_REGISTRY: dict[int, RNSContext] = {}


@lru_cache(maxsize=None)
def _bconv_dispatch(rns_id, src, dst, block, interpret):
    """Rank-polymorphic BConv dispatch + ``custom_vmap`` rule, cached.

    Leading batch dims fold into the kernel grids (batch-major rows,
    constants read via ``%`` index maps) — the vmap rule re-invokes the
    same dispatch on the batched operand, so nothing is replicated."""
    c = _consts(rns_id, src, dst)
    ld = len(dst)
    # numpy (NOT jnp) constants: the closure is cached across traces, so
    # captured values must never be tracers.
    consts = (
        c.qhat_inv_mont, c.src_q, c.src_qneg, c.qhat_mod_mont,
        c.dst_q, c.dst_qneg,
    )

    def dispatch(x):
        n = x.shape[-1]
        y = bconv_pallas(
            x.reshape((-1,) + lane_layout(n)), *consts, block=block,
            interpret=interpret,
        )
        return y.reshape(x.shape[:-2] + (ld, n))

    fn = custom_vmap(dispatch)

    @fn.def_vmap
    def _rule(axis_size, in_batched, x):
        del axis_size, in_batched  # batch axis is at the front: fold it
        return dispatch(x), True

    # jitted: every call with the same operand shapes, in any program,
    # reuses one traced kernel (tracing them dominated plan lowering)
    return jax.jit(fn)


def bconv_kernel(x, src, dst, rns: RNSContext, block: int = 0,
                 interpret: bool | None = None):
    """(..., ls, N) uint32 -> (..., ld, N) uint32 via the Pallas kernel.
    ``jax.vmap``-safe via a ``custom_vmap`` rule."""
    if interpret is None:
        interpret = default_interpret()
    _RNS_REGISTRY[id(rns)] = rns
    return _bconv_dispatch(
        id(rns), tuple(src), tuple(dst), int(block), bool(interpret)
    )(x.astype(jnp.uint32))


def bconv_oracle(x, src, dst, rns: RNSContext):
    _RNS_REGISTRY[id(rns)] = rns
    c = _consts(id(rns), tuple(src), tuple(dst))
    return _ref.bconv_ref(
        x, jnp.asarray(c.qhat_inv), jnp.asarray(c.src_q),
        jnp.asarray(c.qhat_mod), jnp.asarray(c.dst_q),
    )
