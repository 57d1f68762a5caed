"""jit'd wrapper for the fused IP kernel."""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.custom_batching import custom_vmap

from repro.kernels.fused_ip.fused_ip import fused_ip_pallas
from repro.kernels.fused_ip import ref as _ref
from repro.kernels.modops import (
    default_interpret, lane_layout, qinv_neg_host, to_mont_host,
)


def _mont(arr: np.ndarray, q: np.ndarray) -> np.ndarray:
    """Montgomery-convert along the limb axis (q broadcast per row)."""
    out = np.empty(arr.shape, dtype=np.uint32)
    it = np.ndindex(*arr.shape[:-2])
    for idx in it:
        for r in range(arr.shape[-2]):
            out[idx + (r,)] = to_mont_host(
                arr[idx + (r,)].astype(np.uint64), int(q[r])
            )
    return out


@lru_cache(maxsize=None)
def _ip_dispatch(with_pt: bool, interpret: bool):
    """Rank-polymorphic fused-IP dispatch + ``custom_vmap`` rule.

    Leading batch dims on ``digits`` fold into the kernel's row/grid
    axis (batch-major, ``% l`` index maps for the unbatched evk/pt/
    modulus operands).  Only the digits operand may carry a vmap axis —
    evk, plaintext and moduli are shared per-plan constants."""

    def dispatch(digits, evk, pt, q, qneg):
        l = q.shape[0]
        n = digits.shape[-1]
        dnum = digits.shape[-3]
        lead = digits.shape[:-3]
        lay = lane_layout(n)
        d = digits.reshape((-1,) + digits.shape[-3:])      # (B, dnum, l, n)
        d = jnp.moveaxis(d, 0, 1).reshape((dnum, -1) + lay)  # (dnum, B*l, ..)
        a0, a1 = fused_ip_pallas(
            d, evk.reshape(evk.shape[:-1] + lay),
            pt.reshape((l,) + lay) if with_pt else None, q, qneg,
            interpret=interpret,
        )
        return a0.reshape(lead + (l, n)), a1.reshape(lead + (l, n))

    fn = custom_vmap(dispatch)

    @fn.def_vmap
    def _rule(axis_size, in_batched, digits, evk, pt, q, qneg):
        del axis_size
        if any(in_batched[1:]):
            raise NotImplementedError(
                "fused_ip: only the digits operand may be vmapped; evk/"
                "plaintext/moduli are per-plan constants")
        return dispatch(digits, evk, pt, q, qneg), (True, True)

    # jitted: every call with the same operand shapes, in any program,
    # reuses one traced kernel (tracing them dominated plan lowering)
    return jax.jit(fn)


def fused_ip_mont(digits, evk_mont, pt_mont, q, qneg,
                  interpret: bool | None = None):
    """Deployment-shaped entry: evk/pt are ALREADY Montgomery uint32
    (stored pre-converted, e.g. by the keyswitch engine's per-context
    cache); digits stay normal-form, shape (..., dnum, l, N) — leading
    batch dims (or a ``jax.vmap`` axis) are folded into the kernel grid.
    q/qneg: (l,) uint32.  ``pt_mont=None`` runs the kernel variant
    without a plaintext operand."""
    if interpret is None:
        interpret = default_interpret()
    with_pt = pt_mont is not None
    return _ip_dispatch(with_pt, bool(interpret))(
        digits, evk_mont, pt_mont, q, qneg
    )


def fused_ip_kernel(digits, evk, pt, q, interpret: bool | None = None):
    """NORMAL-form inputs; conversion to Montgomery happens here (in a
    real deployment evk/pt are stored pre-converted — see
    ``fused_ip_mont``)."""
    qv = np.asarray(q, dtype=np.uint32)
    evk_m = _mont(np.asarray(evk), qv)
    pt_m = _mont(np.asarray(pt)[None], qv)[0] if pt is not None else None
    qneg = np.array([qinv_neg_host(int(x)) for x in qv], dtype=np.uint32)
    return fused_ip_mont(
        jnp.asarray(np.asarray(digits, dtype=np.uint32)),
        jnp.asarray(evk_m),
        jnp.asarray(pt_m) if pt_m is not None else None,
        jnp.asarray(qv),
        jnp.asarray(qneg),
        interpret=interpret,
    )


def fused_ip_oracle(digits, evk, pt, q):
    return _ref.fused_ip_ref(
        jnp.asarray(np.asarray(digits, dtype=np.uint32)),
        jnp.asarray(np.asarray(evk, dtype=np.uint32)),
        jnp.asarray(np.asarray(pt, dtype=np.uint32)) if pt is not None else None,
        jnp.asarray(np.asarray(q, dtype=np.uint32)),
    )
