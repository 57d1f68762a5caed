"""jit'd wrappers + per-limb table precomputation for the NTT kernel."""
from __future__ import annotations

from functools import lru_cache
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.custom_batching import custom_vmap

from repro.core.rns import RNSContext, rns_context
from repro.kernels.modops import (
    default_interpret, lane_layout, qinv_neg_host, to_mont_host,
)
from repro.kernels.ntt.ntt import ntt_pallas, pack_twiddles
from repro.kernels.ntt import ref as _ref


class NTTKernelTables:
    """Stacked per-limb uint32 tables: normal-form flat tables for the
    oracle, Montgomery-form kernel tables in the kernel's lane-dense
    layout (twists ``(l, R, lanes)``, packed twiddles ``(l, R +
    AUX_ROWS, lanes)``, see ``kernels/ntt/ntt.py``)."""

    def __init__(self, rns: RNSContext):
        self.rns = rns
        self.logn = rns.params.logN
        n = rns.params.N
        primes = rns.all_primes
        l = len(primes)

        def flat_tw(stage_list, pi):
            out = np.ones(n, dtype=np.uint64)
            for s, tws in enumerate(stage_list):
                m = 1 << s
                out[m : 2 * m] = tws[pi]
            return out

        tw_f = np.stack([flat_tw(rns.stage_tw, i) for i in range(l)])
        tw_i = np.stack([flat_tw(rns.stage_tw_inv, i) for i in range(l)])
        twist_f = rns.psi_pows.astype(np.uint64)
        twist_i = (
            rns.psi_inv_pows * rns.n_inv[:, None] % rns.moduli[:, None]
        )

        self.layout = lane_layout(n)
        self.q = rns.moduli.astype(np.uint32)
        self.qinv = np.array(
            [qinv_neg_host(int(p)) for p in primes], dtype=np.uint32
        )
        # normal-form tables (for the oracle)
        self.tw_f = tw_f
        self.tw_i = tw_i
        self.twist_f = twist_f
        self.twist_i = twist_i.astype(np.uint64)
        # Montgomery-form tables (for the kernel), lane-dense
        def mont(tab):
            return np.stack(
                [to_mont_host(tab[i], int(primes[i])) for i in range(l)])

        self.tw_f_m = np.stack([pack_twiddles(t) for t in mont(tw_f)])
        self.tw_i_m = np.stack([pack_twiddles(t) for t in mont(tw_i)])
        self.twist_f_m = mont(twist_f).reshape((l,) + self.layout)
        self.twist_i_m = mont(self.twist_i).reshape((l,) + self.layout)

    def rows(self, primes: tuple[int, ...]) -> np.ndarray:
        return self.rns.limb_ids(primes)

    def device(self) -> "KernelTables":
        """Device copies of the kernel tables, on the default device."""
        return KernelTables(*(jnp.asarray(a) for a in (
            self.twist_f_m, self.tw_f_m, self.twist_i_m, self.tw_i_m,
            self.q, self.qinv)))


class KernelTables(NamedTuple):
    """Every limb's Montgomery NTT tables on the device, rows in
    ``RNSContext.all_primes`` order.

    A pytree: jitted programs take it as an argument, so no compiled
    program embeds the tables (~1 MB per limb at logN=16) as constants.
    ``take`` selects limb rows inside the caller's trace."""
    twist_f: jax.Array      # (n_limbs, R, lanes)
    tw_f: jax.Array         # (n_limbs, R + AUX_ROWS, lanes)
    twist_i: jax.Array
    tw_i: jax.Array
    q: jax.Array            # (n_limbs,) uint32
    qinv: jax.Array

    def take(self, rows: tuple[int, ...], inverse: bool) -> tuple:
        """(twist, tw, q, qinv) of one direction for limb ``rows``."""
        if inverse:
            tabs = (self.twist_i, self.tw_i, self.q, self.qinv)
        else:
            tabs = (self.twist_f, self.tw_f, self.q, self.qinv)
        lo = rows[0]
        if rows == tuple(range(lo, lo + len(rows))):
            return tuple(t[lo : lo + len(rows)] for t in tabs)
        idx = np.array(rows, dtype=np.int32)
        return tuple(t[idx] for t in tabs)


@lru_cache(maxsize=8)
def tables_for(params) -> NTTKernelTables:
    return NTTKernelTables(rns_context(params))


def limb_rows(tables: NTTKernelTables, primes) -> tuple[int, ...]:
    """Table rows of ``primes``.  A tiled selection (one basis repeated,
    as batched multi-poly transforms pass it) is folded to one period:
    the kernels read their tables through ``% l`` index maps."""
    primes = tuple(primes)
    n = len(primes)
    for m in range(1, n):
        if n % m == 0 and primes == primes[:m] * (n // m):
            primes = primes[:m]
            break
    return tuple(int(i) for i in tables.rows(primes))


@lru_cache(maxsize=None)
def _ntt_dispatch(logn: int, inverse: bool, interpret: bool):
    """Rank-polymorphic NTT dispatch + ``custom_vmap`` rule, cached per
    (ring, direction, backend).

    Leading batch dims fold into the kernel's row/grid axis; the limb
    tables are read through ``% l`` index maps, so a ``jax.vmap``-batched
    transform materializes nothing — the vmap rule just re-invokes the
    same dispatch on the batched operand (nesting-safe).  The tables are
    operands, never captured constants; only ``x`` may be batched."""
    layout = lane_layout(1 << logn)

    def dispatch(x, twist, tw, q, qinv):
        y = ntt_pallas(
            x.reshape((-1,) + layout), twist, tw, q, qinv,
            logn=logn, inverse=inverse, interpret=interpret,
        )
        return y.reshape(x.shape)

    fn = custom_vmap(dispatch)

    @fn.def_vmap
    def _rule(axis_size, in_batched, x, *tables):
        del axis_size  # batch axis is at the front: fold it
        if any(in_batched[1:]):
            raise NotImplementedError("ntt: only x may be vmapped")
        return dispatch(x, *tables), True

    # jitted: every call with the same operand shapes, in any program,
    # reuses one traced kernel (tracing them dominated plan lowering)
    return jax.jit(fn)


def _transform(x, primes, tables, interpret, dev, inverse):
    if interpret is None:
        interpret = default_interpret()
    if dev is None:
        dev = tables.device()
    tabs = dev.take(limb_rows(tables, primes), inverse)
    return _ntt_dispatch(tables.logn, inverse, bool(interpret))(
        x.astype(jnp.uint32), *tabs)


def ntt_fwd(x, primes, tables: NTTKernelTables,
            interpret: bool | None = None, dev: KernelTables | None = None):
    """(..., l, N) uint32 natural coeffs -> bit-reversed eval order.

    ``primes`` may be one basis tiled (batched multi-poly transforms).
    ``dev``: the device tables (``tables.device()``); a jitted caller
    passes them in as an argument.  ``interpret=None`` auto-detects the
    backend.  ``jax.vmap``-safe via a ``custom_vmap`` rule.
    """
    return _transform(x, primes, tables, interpret, dev, inverse=False)


def ntt_inv(x, primes, tables: NTTKernelTables,
            interpret: bool | None = None, dev: KernelTables | None = None):
    return _transform(x, primes, tables, interpret, dev, inverse=True)


def ntt_fwd_oracle(x, primes, tables: NTTKernelTables):
    r = tables.rows(tuple(primes))
    return _ref.ntt_fwd_ref(
        x, jnp.asarray(tables.twist_f[r]), jnp.asarray(tables.tw_f[r]),
        jnp.asarray(tables.q[r, None].astype(np.uint64)),
    )


def ntt_inv_oracle(x, primes, tables: NTTKernelTables):
    r = tables.rows(tuple(primes))
    return _ref.ntt_inv_ref(
        x, jnp.asarray(tables.twist_i[r]), jnp.asarray(tables.tw_i[r]),
        jnp.asarray(tables.q[r, None].astype(np.uint64)),
    )
