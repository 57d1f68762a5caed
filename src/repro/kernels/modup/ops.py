"""jit'd wrapper, constants, and vmap rule for the fused ModUp kernel."""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.custom_batching import custom_vmap

from repro.core.rns import RNSContext
from repro.kernels.modops import (
    default_interpret, lane_layout, mont_mul, to_mont_host,
)
from repro.kernels.modup import ref as _ref
from repro.kernels.modup.modup import modup_pallas
from repro.kernels.ntt.ops import KernelTables, NTTKernelTables


class ModUpDigitConsts:
    """Per-(source digit, destination basis) constants.

    The BConv per-limb scale ``qhat_inv_i`` is folded into the INTT
    post-twist: the kernel then needs no scale pass at all.  The kernel's
    scaled table is derived on the device from the context's NTT tables
    (``scaled_twist``); the normal-form copy here, exact in uint64
    (residues < 2^31, so products fit), feeds the oracle."""

    def __init__(self, rns: RNSContext, tabs: NTTKernelTables,
                 src: tuple[int, ...], dst: tuple[int, ...]):
        qhat_inv, qhat_mod = rns.bconv_consts(tuple(src), tuple(dst))
        rs = tabs.rows(tuple(src))
        rd = tabs.rows(tuple(dst))
        ld = len(dst)
        self.src_rows = tuple(int(i) for i in rs)
        self.dst_rows = tuple(int(i) for i in rd)
        self.qhat_inv_mont = np.array(             # (ls,), Montgomery
            [int(to_mont_host(np.array([qhat_inv[i]]), int(q))[0])
             for i, q in enumerate(src)], dtype=np.uint32)
        self.twist_i_scaled = (tabs.twist_i[rs]
                               * qhat_inv.astype(np.uint64)[:, None]
                               % tabs.q[rs, None].astype(np.uint64))
        self.qhat_mod = qhat_mod
        self.c_mont = np.stack([              # (ls, ld), SMEM
            np.array(
                [int(to_mont_host(np.array([qhat_mod[i, j]]),
                                  int(dst[j]))[0])
                 for j in range(ld)],
                dtype=np.uint32,
            )
            for i in range(len(src))
        ])
        # normal-form tables for the oracle
        self.src_q = tabs.q[rs]
        self.dst_q = tabs.q[rd]
        self.tw_i = tabs.tw_i[rs]
        self.twist_f = tabs.twist_f[rd]
        self.tw_f = tabs.tw_f[rd]
        self.logn = tabs.logn

    def kernel_tables(self, dev: KernelTables) -> tuple:
        """The kernel's table operands, taken from the device NTT tables
        inside the caller's trace: (twist_i_scaled, tw_i, src_q,
        src_qneg, c_mont, twist_f, tw_f, dst_q, dst_qneg)."""
        twist_i, tw_i, sq, sqn = dev.take(self.src_rows, inverse=True)
        col = (slice(None), None, None)
        scaled = mont_mul(twist_i, jnp.asarray(self.qhat_inv_mont)[col],
                          sq[col], sqn[col])
        twist_f, tw_f, dq, dqn = dev.take(self.dst_rows, inverse=False)
        return (scaled, tw_i, sq, sqn, jnp.asarray(self.c_mont),
                twist_f, tw_f, dq, dqn)


_REGISTRY: dict[tuple, tuple] = {}


def _admit(rns: RNSContext, tabs: NTTKernelTables) -> tuple:
    key = (id(rns), id(tabs))
    _REGISTRY[key] = (rns, tabs)
    return key


@lru_cache(maxsize=None)
def _consts(reg_key, src, dst) -> ModUpDigitConsts:
    rns, tabs = _REGISTRY[reg_key]
    return ModUpDigitConsts(rns, tabs, src, dst)


@lru_cache(maxsize=None)
def _dispatch(logn: int, ld: int, interpret: bool):
    """Rank-polymorphic dispatch + ``custom_vmap`` rule, cached so every
    trace of the same (ring, basis width, backend) reuses ONE callable.

    The dispatch flattens any leading batch dims into the kernel's grid
    axis (batch-major rows) — zero extra materialization — so the vmap
    rule simply re-invokes it on the batched operand.  The tables are
    operands, never captured constants; only ``x`` may be batched."""
    layout = lane_layout(1 << logn)

    def dispatch(x, *tables):
        y = modup_pallas(
            x.reshape((-1,) + layout), *tables, logn=logn,
            interpret=interpret,
        )
        return y.reshape(x.shape[:-2] + (ld, x.shape[-1]))

    fn = custom_vmap(dispatch)

    @fn.def_vmap
    def _rule(axis_size, in_batched, x, *tables):
        del axis_size  # batch axis is at the front: fold it
        if any(in_batched[1:]):
            raise NotImplementedError("modup: only x may be vmapped")
        return dispatch(x, *tables), True

    # jitted: every call with the same operand shapes, in any program,
    # reuses one traced kernel (tracing them dominated plan lowering)
    return jax.jit(fn)


def modup_digit(x, src, dst, tabs: NTTKernelTables, rns: RNSContext,
                interpret: bool | None = None,
                dev: KernelTables | None = None):
    """(..., ls, N) uint32 bit-reversed eval -> (..., ld, N) bit-reversed
    eval: ONE fused pallas_call (INTT -> scaled tree-reduce -> NTT) per
    digit, VMEM-resident across all three phases.  ``dev``: the device
    NTT tables (``tabs.device()``); a jitted caller passes them in as an
    argument.  ``jax.vmap``-safe."""
    if interpret is None:
        interpret = default_interpret()
    if dev is None:
        dev = tabs.device()
    c = _consts(_admit(rns, tabs), tuple(src), tuple(dst))
    return _dispatch(c.logn, len(dst), bool(interpret))(
        x.astype(jnp.uint32), *c.kernel_tables(dev))


def modup_digit_oracle(x, src, dst, tabs: NTTKernelTables,
                       rns: RNSContext):
    """Exact uint64 mirror of :func:`modup_digit` (same phase fusion)."""
    key = _admit(rns, tabs)
    c = _consts(key, tuple(src), tuple(dst))
    return _ref.modup_digit_ref(
        x, jnp.asarray(c.twist_i_scaled), jnp.asarray(c.tw_i),
        jnp.asarray(c.src_q[:, None].astype(np.uint64)),
        jnp.asarray(c.qhat_mod),
        jnp.asarray(c.twist_f), jnp.asarray(c.tw_f),
        jnp.asarray(c.dst_q[:, None].astype(np.uint64)),
    )
