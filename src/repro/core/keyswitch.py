"""Batched, jit-compiled keyswitch engine: ModUp -> IP -> ModDown.

The seed runtime executes keyswitch as per-digit / per-rotation Python
loops over generic uint64 ``%`` jnp ops.  This engine replaces that hot
path with one traced program per ``(level, dnum)`` plan:

  * digits live as ONE stacked ``(dnum, l_ext, N)`` tensor — ModUp is a
    single batched INTT over all base limbs, a block-diagonal BConv
    contraction (per-digit constants packed into one ``(dnum, alpha,
    l_ext)`` tensor), and one batched NTT over all dnum x l_ext new
    limbs, with own-limb passthrough applied as a gather + where;
  * the inner product is one fused contraction against the pre-stacked
    evk tensor ``(dnum, 2, l_ext, N)`` — the ``kernels/fused_ip``
    layout;
  * hoisted rotations apply automorphisms IN THE EVAL DOMAIN via one
    precomputed gather-index tensor ``(R, N)`` covering all digits and
    rotations (see ``RNSContext.autom_eval_perm``) — no per-rotation
    INTT/NTT round trips;
  * ModDown runs batched over both accumulator polynomials at once.

Every plan traces once under ``jax.jit`` and is cached; re-dispatch at
the same level is a cache hit (``trace_counts`` records trace events).
The cache key is the full dispatch SHAPE — op, level/dnum, hoisted term
count, and (for the ``*_batched`` entry points) the leading batch
width — and never key material: evk and plaintext tensors are separate
per-``id(evk)`` device caches resolved at dispatch time.  One traced
plan therefore serves every ciphertext owner; the multi-tenant serving
layer (``repro.serve``) leans on exactly this split, sharing one
engine's plans across tenants while swapping per-tenant ``KeyChain``s
underneath, and treats ``(plan signature, batch width)`` as its
admission-policy object (``docs/SERVING.md``).

The compiled runtime (``repro.runtime``) drives three extensions of the
same plans: ``modup``/``digits=`` split the hoisted entry point so one
ModUp feeds every block anchored on the same ciphertext (callers pass
``digits=`` to reuse a prior ``modup``'s stacked ``(dnum, l_ext, N)``
tensor instead of paying a fresh ModUp), the ``*_batched`` entry
points ``jax.vmap`` a whole batch of independent ciphertexts through
one trace (either backend; a new batch width is a new trace, hence the
serving layer's fixed-width padding), and every dispatch tallies
``OpCounters`` so reports can reconcile executed ModUp/ModDown/IP
counts against ``dfg.hoist`` predictions.

Relinearization is the second member of the keyswitch family and runs
on the SAME plan caches: ``relin``/``relin_batched`` keyswitch the d2
tensor-product component against the mult key (accepting pre-computed
``digits=`` exactly like the hoisted rotations), and
``multi_relin_sum(_batched)`` accumulates the IPs of several relin
terms in the extended basis and closes them with ONE batched ModDown —
the relin analogue of ``multi_hoisted_rotation_sum`` (ARK-style lazy
ModDown), driven by ``runtime.lower.MultiRelinStep``.

Backends (``PolyContext.backend``):
  * ``"jnp"``    — exact uint64 ``(a * b) % q`` ops (``PolyContext.modq``),
    batched as above.
  * ``"pallas"`` — uint32 Montgomery Pallas kernel suite.  ModUp runs
    the FUSED kernel (``kernels/modup``): one ``pallas_call`` per digit
    executes INTT -> BConv tree-reduce -> NTT with the digit
    VMEM-resident across all three phases (the BConv scale folded into
    the INTT post-twist), no per-phase HBM intermediates.  ModDown and
    the inner product dispatch ``kernels/ntt``/``kernels/bconv``/
    ``kernels/fused_ip``; ``interpret=True`` off-TPU.  The kernels'
    bit-reversed eval order is bridged to the core's natural order by a
    single ``bitrev`` permutation at kernel boundaries; Montgomery evk /
    plaintext tables are built once per context and cached.  Every
    kernel wrapper carries a ``jax.custom_vmap`` rule folding the batch
    axis into its grid, so the ``*_batched`` entry points run on either
    backend — the serving layer and compiled runtime batch pallas plans
    exactly like jnp ones.

Both backends are bit-exact with the seed per-digit path on every entry
point, batched included (enforced by ``tests/test_keyswitch_engine.py``
and ``tests/test_relin.py``).
"""
from __future__ import annotations

import copy
from functools import partial, wraps
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import poly
from repro.core.counters import OpCounters
from repro.errors import ModulusChainMismatchError
from repro.kernels.bconv.ops import bconv_kernel
from repro.kernels.fused_ip.ops import fused_ip_mont
from repro.kernels.modops import qinv_neg_host
from repro.kernels.modup.ops import modup_digit
from repro.kernels.ntt.ops import ntt_fwd, ntt_inv

if TYPE_CHECKING:  # avoid importing keys at runtime (ckks -> keyswitch)
    from repro.core.keys import EvalKey

# Source-limb chunk bounding the (dnum, chunk, l_ext, N) BConv
# intermediate — the VMEM-resident working-set analogue of the Pallas
# BConvU's coefficient blocking.
_CHUNK = 8


def plan_name(kind: str, level: int) -> str:
    """Name of a jitted keyswitch plan, ``ks_<kind>_L<level>``: the same
    in every process, so a warm run finds it in the persistent cache,
    and in the compiled program's ops (``jit(ks_galois_L7)/...``)."""
    return f"ks_{kind}_L{level}"


def _scoped(scope: str):
    """Trace a keyswitch phase under ``jax.named_scope(scope)``: its ops
    carry the scope in their op names, and so on the device trace.  The
    phases are ``ks.modup``, ``ks.ip``, ``ks.moddown`` and
    ``ks.automorph`` (the eval-domain gathers, scoped inline)."""
    def wrap(fn):
        @wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(scope):
                return fn(*args, **kwargs)
        return scoped
    return wrap


def ext_rows(params, level: int) -> np.ndarray:
    """Rows of a full-basis (Q_L u P) evk tensor active at ``level``."""
    L, k = params.L, params.k
    return np.concatenate(
        [np.arange(level + 1), np.arange(L + 1, L + 1 + k)]
    )


def _to_mont_host_rows(arr: np.ndarray, q: np.ndarray) -> np.ndarray:
    """Montgomery-convert (..., l, N) uint64 with per-row moduli (l,).

    Exact in uint64 (residues < q < 2^31, so ``x << 32`` < 2^63);
    done once per evk/plaintext and cached by the engine.
    """
    shape = (1,) * (arr.ndim - 2) + (len(q), 1)
    qcol = q.astype(np.uint64).reshape(shape)
    return ((arr.astype(np.uint64) << np.uint64(32)) % qcol).astype(np.uint32)


class KeyswitchPlan:
    """Per-level constants: index tensors, packed BConv constants, mods."""

    def __init__(self, pc: poly.PolyContext, level: int):
        params = pc.params
        rns = pc.rns
        self.level = level
        self.base: tuple[int, ...] = params.q_chain(level)
        self.ext: tuple[int, ...] = self.base + params.p_primes
        self.groups = params.digit_groups(level)
        self.dnum = len(self.groups)
        self.alpha = max(len(D) for D in self.groups)
        self.group_sizes = tuple(len(D) for D in self.groups)
        self.l = len(self.base)
        self.l_ext = len(self.ext)
        self.k = len(params.p_primes)
        self.N = params.N

        # Static primes tuples for batched NTT dispatch (duplicates OK).
        self.ext_tiled = self.ext * self.dnum
        self.p_tiled = params.p_primes * 2
        self.base_tiled = self.base * 2

        # host constants: the TPU reduction derives its per-modulus
        # Montgomery constants from them on the host (``poly._modq_mont``)
        self.base_mods = np.array(self.base, dtype=np.uint64)
        self.ext_mods = np.array(self.ext, dtype=np.uint64)
        self.p_mods = np.array(params.p_primes, dtype=np.uint64)

        # --- ModUp: per-limb scale constants + block-diagonal reduce ---
        qinv = np.zeros(self.l, dtype=np.uint64)
        src_idx = np.zeros((self.dnum, self.alpha), dtype=np.int32)
        C = np.zeros((self.dnum, self.alpha, self.l_ext), dtype=np.uint64)
        row = 0
        for j, D in enumerate(self.groups):
            qhat_inv, qhat_mod = rns.bconv_consts(tuple(D), self.ext)
            for i in range(len(D)):
                qinv[row + i] = qhat_inv[i]
                src_idx[j, i] = row + i
                C[j, i] = qhat_mod[i]
            row += len(D)
        self.qinv = jnp.asarray(qinv)
        self.src_idx = jnp.asarray(src_idx)
        self.C = jnp.asarray(C)

        # Own-limb passthrough: digit j keeps its eval-domain rows.
        own_idx = np.zeros((self.dnum, self.l_ext), dtype=np.int32)
        own_mask = np.zeros((self.dnum, self.l_ext), dtype=bool)
        base_pos = {p: i for i, p in enumerate(self.base)}
        for j, D in enumerate(self.groups):
            for r, p in enumerate(self.ext):
                if p in D:
                    own_idx[j, r] = base_pos[p]
                    own_mask[j, r] = True
        self.own_idx = jnp.asarray(own_idx)
        self.own_mask = jnp.asarray(own_mask)

        # --- ModDown: P -> Q_level conversion constants ---
        md_qhat_inv, md_C = rns.bconv_consts(params.p_primes, self.base)
        self.md_qhat_inv = jnp.asarray(md_qhat_inv)
        self.md_C = jnp.asarray(md_C)                  # (k, l)
        self.pinv = jnp.asarray(rns.p_inv_mod_q(level))

        # --- Pallas backend extras ---
        self.bitrev = np.asarray(pc.rns.bitrev)
        # per-limb modulus pair of the fused IP kernel (SMEM operands)
        self.q32 = np.array(self.ext, dtype=np.uint32)
        self.qneg32 = np.array(
            [qinv_neg_host(q) for q in self.ext], dtype=np.uint32)
        self.pc = pc

    def bind(self, tables: dict) -> "KeyswitchPlan":
        """This plan computing with the context's ``tables`` (the traced
        argument of a jitted plan, see ``PolyContext.bind``)."""
        plan = copy.copy(self)
        plan.pc = self.pc.bind(tables)
        return plan


class KeyswitchEngine:
    """Jit-compiled batched keyswitch over a ``PolyContext``.

    One trace per (level, op-shape); evk tensors stacked (and, for the
    pallas backend, Montgomery-converted) once per key and cached.
    """

    def __init__(self, pc: poly.PolyContext,
                 counters: OpCounters | None = None):
        self.pc = pc
        self.params = pc.params
        self.backend = pc.backend
        self.interpret = pc.interpret
        self.tabs = pc.ntt_tables
        self.counters = counters if counters is not None else OpCounters()
        self._plans: dict[int, KeyswitchPlan] = {}
        self._ks_fns: dict[int, object] = {}
        self._galois_fns: dict[int, object] = {}
        self._hoist_fns: dict[tuple, object] = {}
        self._modup_fns: dict[int, object] = {}
        self._batch_fns: dict[tuple, object] = {}
        self._evk_full: dict[int, tuple] = {}     # id(evk) -> (evk, stacked)
        self._evk_level: dict[tuple, jnp.ndarray] = {}
        self._evk_group: dict[tuple, jnp.ndarray] = {}
        self._perm_cache: dict[tuple, jnp.ndarray] = {}
        self.trace_counts: dict[tuple, int] = {}

    # ------------------------- op counting -----------------------------
    def _note_keyswitch(self, plan: KeyswitchPlan, m: int = 1) -> None:
        c = self.counters
        c.note_modup(plan.l, plan.l_ext, plan.group_sizes, plan.N, m)
        c.note_ip(plan.dnum, plan.l_ext, plan.N, 1, m)
        c.note_moddown(plan.l, plan.k, plan.N, m)
        c.keyswitch += m

    def _note_hoisted(self, plan: KeyswitchPlan, n_rot: int,
                      with_modup: bool, m: int = 1) -> None:
        c = self.counters
        if with_modup:
            c.note_modup(plan.l, plan.l_ext, plan.group_sizes, plan.N, m)
        c.note_ip(plan.dnum, plan.l_ext, plan.N, n_rot, m)
        c.note_moddown(plan.l, plan.k, plan.N, m)
        c.keyswitch += m * n_rot
        c.rotation += m * n_rot
        c.hoisted_blocks += m

    def _note_relin(self, plan: KeyswitchPlan, with_modup: bool,
                    n: int = 1, m: int = 1) -> None:
        """n relinearizations of m ciphertexts sharing one ModDown each
        (n > 1: a merged multi-relin block — ONE ModDown total)."""
        c = self.counters
        if with_modup:
            c.note_modup(plan.l, plan.l_ext, plan.group_sizes, plan.N,
                         m * n)
        c.note_ip(plan.dnum, plan.l_ext, plan.N, n, m)
        c.note_moddown(plan.l, plan.k, plan.N, m)
        c.keyswitch += m * n
        c.relin += m * n
        if n > 1:
            c.relin_blocks += m

    # ------------------------- plans / tracing -------------------------
    def _plan(self, level: int) -> KeyswitchPlan:
        if level not in self._plans:
            self._plans[level] = KeyswitchPlan(self.pc, level)
        return self._plans[level]

    def _count_trace(self, key: tuple) -> None:
        # a repeat trace of the same plan key is a retrace — exactly
        # what the serving layer's zero-retrace gate hunts for
        self.trace_counts[key] = self.trace_counts.get(key, 0) + 1

    def _note_dispatch(self, op: str) -> None:
        """Kernel-dispatch event: lets Perfetto traces tell a pallas
        executor span (fused ModUp kernel, interpret flag recorded) from
        a jnp one (op-by-op uint64) without changing the span labels."""
        obs.event(
            "engine.kernel_dispatch", op=op, backend=self.backend,
            modup="fused" if self.backend == "pallas" else "op-by-op",
            interpret=self.backend == "pallas" and self.interpret,
        )

    # ------------------------- evk stacking ----------------------------
    def _admit_evk(self, evk: EvalKey) -> None:
        """Cache-admission guard: an evk generated under different
        ``CKKSParams`` (wrong digit count or extended-basis shape) must
        be rejected HERE, at the cache boundary, not hoped past — a
        mis-shaped key either crashes deep inside a jit trace or
        silently keyswitches with garbage gadgets.  Runs only on cache
        miss, so the hot path never pays for it."""
        p = self.params
        want_digits = p.dnum
        want_shape = (2, p.L + 1 + p.k, p.N)
        if len(evk.digits) != want_digits:
            raise ModulusChainMismatchError(
                "evk digit count disagrees with the engine's params",
                hint="the key was generated under different CKKSParams; "
                     "regenerate it with this context's KeyChain",
                evk_digits=len(evk.digits), dnum=want_digits)
        got = tuple(evk.digits[0].shape)
        if got != want_shape:
            raise ModulusChainMismatchError(
                "evk digit shape disagrees with the extended basis",
                hint="the key was generated under a different modulus "
                     "chain; regenerate it with this context's KeyChain",
                evk_shape=got, expected=want_shape)

    def _evk_stacked(self, evk: EvalKey) -> jnp.ndarray:
        """(dnum_full, 2, L+1+k, N) uint64, cached per key object."""
        key = id(evk)
        if key not in self._evk_full:
            self._admit_evk(evk)
            self._evk_full[key] = (evk, jnp.stack(evk.digits))
        return self._evk_full[key][1]

    def evk_tensor(self, evk: EvalKey, level: int) -> jnp.ndarray:
        """Level-sliced evk tensor (dnum, 2, l_ext, N) — uint64 for the
        jnp backend, Montgomery uint32 for pallas.  Cached."""
        key = (id(evk), level)
        if key not in self._evk_level:
            plan = self._plan(level)
            full = self._evk_stacked(evk)
            sl = full[: plan.dnum][:, :, ext_rows(self.params, level)]
            if self.backend == "pallas":
                sl = jnp.asarray(_to_mont_host_rows(
                    np.asarray(sl), np.array(plan.ext, dtype=np.uint64)
                ))
            self._evk_level[key] = sl
        return self._evk_level[key]

    def evk_group_tensor(self, evks: list[EvalKey],
                         level: int) -> jnp.ndarray:
        """(R, dnum, 2, l_ext, N) stack for a hoisted rotation group.
        Bounded (FIFO eviction) — rotation groups vary across programs."""
        key = (tuple(id(k) for k in evks), level)
        if key not in self._evk_group:
            while len(self._evk_group) >= 64:
                self._evk_group.pop(next(iter(self._evk_group)))
            self._evk_group[key] = jnp.stack(
                [self.evk_tensor(k, level) for k in evks]
            )
        return self._evk_group[key]

    def perm_tensor(self, galois_list: list[int]) -> jnp.ndarray:
        """(R, N) eval-domain automorphism gather indices."""
        key = tuple(galois_list)
        if key not in self._perm_cache:
            self._perm_cache[key] = jnp.asarray(np.stack(
                [self.pc.rns.autom_eval_perm(g).astype(np.int32)
                 for g in galois_list]
            ))
        return self._perm_cache[key]

    # ------------------------- traced primitives -----------------------
    def _ntt(self, x, primes, plan: KeyswitchPlan):
        """Batched forward NTT, core (natural) eval order in/out."""
        if self.backend == "pallas":
            y = ntt_fwd(x.astype(jnp.uint32), primes, self.tabs,
                        interpret=self.interpret,
                        dev=plan.pc.kernel_tables)
            return poly.fence(y[:, plan.bitrev].astype(jnp.uint64))
        return poly.ntt(x, primes, plan.pc)

    def _intt(self, x, primes, plan: KeyswitchPlan):
        if self.backend == "pallas":
            y = ntt_inv(x[:, plan.bitrev].astype(jnp.uint32), primes,
                        self.tabs, interpret=self.interpret,
                        dev=plan.pc.kernel_tables)
            return poly.fence(y.astype(jnp.uint64))
        return poly.intt(x, primes, plan.pc)

    @_scoped("ks.modup")
    def _modup(self, a, plan: KeyswitchPlan):
        """(l, N) eval -> (dnum, l_ext, N) eval, all digits at once."""
        modq = self.pc.modq
        if self.backend == "pallas":
            # ONE fused pallas_call per digit (kernels/modup): INTT ->
            # BConv tree-reduce -> NTT with the digit VMEM-resident
            # across all three phases — no per-phase HBM intermediates.
            # The bitrev bridge happens ONCE at each boundary; own-limb
            # passthrough stays outside the kernel (shared below).
            x = a[:, plan.bitrev].astype(jnp.uint32)
            digs = []
            row = 0
            for D in plan.groups:
                digs.append(modup_digit(
                    x[row : row + len(D)], tuple(D), plan.ext,
                    self.tabs, self.pc.rns, interpret=self.interpret,
                    dev=plan.pc.kernel_tables,
                ))
                row += len(D)
            conv = poly.fence(
                jnp.stack(digs)[:, :, plan.bitrev].astype(jnp.uint64))
        else:
            coeff = self._intt(a, plan.base, plan)
            t = modq(coeff * plan.qinv[:, None], plan.base_mods[:, None])
            td = t[plan.src_idx]                       # (dnum, alpha, N)
            em = plan.ext_mods[None, :, None]
            conv = jnp.zeros(
                (plan.dnum, plan.l_ext, plan.N), dtype=jnp.uint64
            )
            for i in range(0, plan.alpha, _CHUNK):
                part = modq(
                    td[:, i : i + _CHUNK, None, :]
                    * plan.C[:, i : i + _CHUNK, :, None], em[None])
                conv = modq(conv + part.sum(axis=1), em)
            conv = conv.reshape(plan.dnum * plan.l_ext, plan.N)
            conv = self._ntt(conv, plan.ext_tiled, plan)
            conv = conv.reshape(plan.dnum, plan.l_ext, plan.N)
        own = a[plan.own_idx]                          # (dnum, l_ext, N)
        return jnp.where(plan.own_mask[:, :, None], own, conv)

    @_scoped("ks.ip")
    def _ip(self, digits, evk, plan: KeyswitchPlan):
        """(dnum, l_ext, N) x (dnum, 2, l_ext, N) -> (2, l_ext, N)."""
        if self.backend == "pallas":
            a0, a1 = fused_ip_mont(
                digits.astype(jnp.uint32), evk, None, plan.q32, plan.qneg32,
                interpret=self.interpret,
            )
            return poly.fence(jnp.stack([a0, a1]).astype(jnp.uint64))
        modq = self.pc.modq
        em = plan.ext_mods[None, None, :, None]
        prod = modq(digits[:, None] * evk, em)        # (dnum, 2, l_ext, N)
        return modq(prod.sum(axis=0), em[0])

    @_scoped("ks.moddown")
    def _moddown2(self, acc, plan: KeyswitchPlan):
        """Batched ModDown of both accumulators: (2, l_ext, N) -> (2, l, N)."""
        modq = self.pc.modq
        xq, xp = acc[:, : plan.l], acc[:, plan.l :]
        xpc = self._intt(
            xp.reshape(2 * plan.k, plan.N), plan.p_tiled, plan
        )
        bm = plan.base_mods[None, :, None]
        if self.backend == "pallas":
            conv = poly.fence(jnp.stack([
                bconv_kernel(
                    xpc[c * plan.k : (c + 1) * plan.k].astype(jnp.uint32),
                    self.params.p_primes, plan.base, self.pc.rns,
                    interpret=self.interpret,
                )
                for c in range(2)
            ]).astype(jnp.uint64))
        else:
            xpc = xpc.reshape(2, plan.k, plan.N)
            t = modq(xpc * plan.md_qhat_inv[None, :, None],
                     plan.p_mods[None, :, None])
            conv = jnp.zeros((2, plan.l, plan.N), dtype=jnp.uint64)
            for i in range(0, plan.k, _CHUNK):
                part = modq(
                    t[:, i : i + _CHUNK, None, :]
                    * plan.md_C[None, i : i + _CHUNK, :, None], bm[:, None])
                conv = modq(conv + part.sum(axis=1), bm)
        conv = self._ntt(
            conv.reshape(2 * plan.l, plan.N), plan.base_tiled, plan
        ).reshape(2, plan.l, plan.N)
        diff = modq(xq + bm - conv, bm)
        return modq(diff * plan.pinv[None, :, None], bm)

    # ------------------------- jitted entry points ---------------------
    def _jit(self, kind: str, level: int, fn):
        """``jax.jit`` of ``fn(plan, *args)`` over ``level``'s plan,
        named ``ks_<kind>_L<level>`` (``plan_name``).

        The context's tables are an argument of the compiled program,
        bound onto the plan inside the trace (``PolyContext.bind``)."""
        plan = self._plan(level)

        def run(tables, *args):
            return fn(plan.bind(tables), *args)

        run.__name__ = run.__qualname__ = plan_name(kind, level)
        return partial(jax.jit(run), self.pc.tables)

    def _ks_fn(self, level: int):
        if level not in self._ks_fns:
            def fn(plan, a, evk):
                self._count_trace(("keyswitch", level))
                digits = self._modup(a, plan)
                d = self._moddown2(self._ip(digits, evk, plan), plan)
                return d[0], d[1]

            self._ks_fns[level] = self._jit("keyswitch", level, fn)
        return self._ks_fns[level]

    def _galois_fn(self, level: int):
        if level not in self._galois_fns:
            def fn(plan, c0, c1, perm, evk):
                self._count_trace(("galois", level))
                with jax.named_scope("ks.automorph"):
                    c0p, c1p = c0[:, perm], c1[:, perm]
                digits = self._modup(c1p, plan)
                d = self._moddown2(self._ip(digits, evk, plan), plan)
                bm = plan.base_mods[:, None]
                return self.pc.modq(c0p + d[0], bm), d[1]

            self._galois_fns[level] = self._jit("galois", level, fn)
        return self._galois_fns[level]

    def _hoist_core(self, plan: KeyswitchPlan, n_rot: int, with_pt: bool,
                    c0, digits, perms, evk_all, pm_ext, pm_base, pm_ext_m):
        """Hoisted-rotation-sum body AFTER ModUp: rotate digits, IP,
        accumulate, one batched ModDown.  Shared by the monolithic,
        digits-in and vmap-batched entry points (bit-exact across all)."""
        modq = self.pc.modq
        # One gather rotates ALL digits for ALL rotations.
        with jax.named_scope("ks.automorph"):
            d_rot = jnp.transpose(
                digits[:, :, perms], (2, 0, 1, 3)
            )                                  # (R, dnum, l_ext, N)
        em = plan.ext_mods[None, :, None]
        with jax.named_scope("ks.ip"):
            if self.backend == "pallas":
                acc = None
                for r in range(n_rot):
                    a0, a1 = fused_ip_mont(
                        d_rot[r].astype(jnp.uint32), evk_all[r],
                        pm_ext_m[r] if with_pt else None,
                        plan.q32, plan.qneg32, interpret=self.interpret,
                    )
                    ipr = jnp.stack([a0, a1]).astype(jnp.uint64)
                    acc = ipr if acc is None else modq(acc + ipr, em)
            else:
                prod = modq(d_rot[:, :, None] * evk_all, em[None, None])
                ip = modq(prod.sum(axis=1), em[None])   # (R, 2, l_ext, N)
                if with_pt:
                    ip = modq(ip * pm_ext[:, None], em[None])
                acc = modq(ip.sum(axis=0), em)
        bm = plan.base_mods[None, :, None]
        with jax.named_scope("ks.automorph"):
            c0r = jnp.transpose(c0[:, perms], (1, 0, 2))  # (R, l, N)
        if with_pt:
            c0r = modq(c0r * pm_base, bm)
        base0 = modq(c0r.sum(axis=0), plan.base_mods[:, None])
        d = self._moddown2(acc, plan)
        return modq(base0 + d[0], plan.base_mods[:, None]), d[1]

    def _hoist_fn(self, level: int, n_rot: int, with_pt: bool):
        key = (level, n_rot, with_pt)
        if key not in self._hoist_fns:
            def fn(plan, c0, c1, perms, evk_all, pm_ext, pm_base, pm_ext_m):
                self._count_trace(("hoisted", level, n_rot, with_pt))
                digits = self._modup(c1, plan)
                return self._hoist_core(plan, n_rot, with_pt, c0, digits,
                                        perms, evk_all, pm_ext, pm_base,
                                        pm_ext_m)

            self._hoist_fns[key] = self._jit("hoisted", level, fn)
        return self._hoist_fns[key]

    def _hoist_digits_fn(self, level: int, n_rot: int, with_pt: bool):
        """Hoisted sum from PRE-COMPUTED digits — the runtime shares one
        ModUp across sibling blocks anchored on the same ciphertext."""
        key = ("digits", level, n_rot, with_pt)
        if key not in self._hoist_fns:
            def fn(plan, c0, digits, perms, evk_all, pm_ext, pm_base,
                   pm_ext_m):
                self._count_trace(("hoisted_digits", level, n_rot, with_pt))
                return self._hoist_core(plan, n_rot, with_pt, c0, digits,
                                        perms, evk_all, pm_ext, pm_base,
                                        pm_ext_m)

            self._hoist_fns[key] = self._jit("hoisted_digits", level, fn)
        return self._hoist_fns[key]

    @_scoped("ks.ip")
    def _acc_ip_ext(self, plan: KeyswitchPlan, n: int, d_terms, evk_all):
        """sum_r IP(d_terms[r], evk_all[r]) in the extended basis.

        ``d_terms``: (n, dnum, l_ext, N); ``evk_all``: (n | 1, dnum, 2,
        l_ext, N) — a leading 1 broadcasts ONE shared evk (the relin
        mult key) over every term.  The single accumulation body behind
        both merged-ModDown flavors (multi-anchor rotation sums and
        multi-relin closures), on either backend."""
        modq = self.pc.modq
        em = plan.ext_mods[None, :, None]
        if self.backend == "pallas":
            shared = evk_all.shape[0] == 1
            acc = None
            for r in range(n):
                a0, a1 = fused_ip_mont(
                    d_terms[r].astype(jnp.uint32),
                    evk_all[0] if shared else evk_all[r], None,
                    plan.q32, plan.qneg32, interpret=self.interpret,
                )
                ipr = jnp.stack([a0, a1]).astype(jnp.uint64)
                acc = ipr if acc is None else modq(acc + ipr, em)
            return acc
        prod = modq(d_terms[:, :, None] * evk_all, em[None, None])
        ip = modq(prod.sum(axis=1), em[None])         # (n, 2, l_ext, N)
        return modq(ip.sum(axis=0), em)

    def _multi_core(self, plan: KeyswitchPlan, n: int, c0s, digits,
                    perms, evk_all):
        """Multi-anchor accumulation body: rotate each anchor's digits
        by ITS perm, IP against ITS evk, accumulate every term in the
        extended basis, and close with ONE batched ModDown."""
        modq = self.pc.modq
        with jax.named_scope("ks.automorph"):
            d_rot = jax.vmap(lambda d, p: d[:, :, p])(digits, perms)
            c0r = jax.vmap(lambda c, p: c[:, p])(c0s, perms)   # (n, l, N)
        acc = self._acc_ip_ext(plan, n, d_rot, evk_all)
        bm = plan.base_mods[:, None]
        base0 = modq(c0r.sum(axis=0), bm)
        d = self._moddown2(acc, plan)
        return modq(base0 + d[0], bm), d[1]

    def _multi_fn(self, level: int, n: int):
        key = ("multi", level, n)
        if key not in self._hoist_fns:
            def fn(plan, c0s, digits, perms, evk_all):
                self._count_trace(("multi_hoisted", level, n))
                return self._multi_core(plan, n, c0s, digits, perms,
                                        evk_all)

            self._hoist_fns[key] = self._jit("multi_hoisted", level, fn)
        return self._hoist_fns[key]

    # ------------------------- relinearization -------------------------
    # Relin is the OTHER member of the keyswitch family: same ModUp ->
    # IP -> ModDown datapath, with the d2 tensor-product component in
    # the role of the rotated c1 and the (single, program-wide) mult key
    # in the role of the per-step rotation keys.  The entry points below
    # reuse the same KeyswitchPlan/jit caches and the same
    # ``modup``/``digits=`` digits interface as the hoisted rotations.
    def _relin_core(self, plan: KeyswitchPlan, d0, d1, digits, evk):
        """IP + ModDown of relin digits, folded into (d0, d1)."""
        d = self._moddown2(self._ip(digits, evk, plan), plan)
        bm = plan.base_mods[:, None]
        return self.pc.modq(d0 + d[0], bm), self.pc.modq(d1 + d[1], bm)

    def _relin_fn(self, level: int, digits_in: bool):
        key = ("relin", level, digits_in)
        if key not in self._hoist_fns:
            def fn(plan, d0, d1, x, evk):
                self._count_trace(("relin", level, digits_in))
                digits = x if digits_in else self._modup(x, plan)
                return self._relin_core(plan, d0, d1, digits, evk)

            self._hoist_fns[key] = self._jit("relin", level, fn)
        return self._hoist_fns[key]

    def _multi_relin_core(self, plan: KeyswitchPlan, n: int, d0s, d1s,
                          digits, evk):
        """Multi-relin accumulation body: every term's IP (against the
        SHARED mult key) accumulates in the extended basis; ONE batched
        ModDown closes the sum — the relin analogue of ``_multi_core``
        (ARK-style lazy/deferred ModDown over summed relin outputs)."""
        modq = self.pc.modq
        acc = self._acc_ip_ext(plan, n, digits, evk[None])
        bm = plan.base_mods[:, None]
        base0 = modq(d0s.sum(axis=0), bm)
        base1 = modq(d1s.sum(axis=0), bm)
        d = self._moddown2(acc, plan)
        return modq(base0 + d[0], bm), modq(base1 + d[1], bm)

    def _multi_relin_fn(self, level: int, n: int):
        key = ("multi_relin", level, n)
        if key not in self._hoist_fns:
            def fn(plan, d0s, d1s, digits, evk):
                self._count_trace(("multi_relin", level, n))
                return self._multi_relin_core(plan, n, d0s, d1s, digits,
                                              evk)

            self._hoist_fns[key] = self._jit("multi_relin", level, fn)
        return self._hoist_fns[key]

    def _modup_fn(self, level: int):
        if level not in self._modup_fns:
            def fn(plan, a):
                self._count_trace(("modup", level))
                return self._modup(a, plan)

            self._modup_fns[level] = self._jit("modup", level, fn)
        return self._modup_fns[level]

    # ------------------------- batched (vmap) fns ----------------------
    def _batched_fn(self, key: tuple, fn):
        """jit(vmap) plan cache: one trace per (op, level, shape) plan —
        re-dispatch at the same batch shape is a cache hit (asserted by
        ``trace_counts``, which only increments while tracing).  ``key``
        is ``(kind, level, ...)``."""
        if key not in self._batch_fns:
            self._batch_fns[key] = self._jit(key[0], key[1], fn)
        return self._batch_fns[key]

    def _ks_batched_fn(self, level: int):
        def fn(plan, ab, evk):
            self._count_trace(("keyswitch_b", level))

            def one(a):
                digits = self._modup(a, plan)
                d = self._moddown2(self._ip(digits, evk, plan), plan)
                return d[0], d[1]

            return jax.vmap(one)(ab)

        return self._batched_fn(("keyswitch_b", level), fn)

    def _galois_batched_fn(self, level: int):
        def fn(plan, c0b, c1b, perm, evk):
            self._count_trace(("galois_b", level))
            bm = plan.base_mods[:, None]

            def one(c0, c1):
                with jax.named_scope("ks.automorph"):
                    c0p, c1p = c0[:, perm], c1[:, perm]
                digits = self._modup(c1p, plan)
                d = self._moddown2(self._ip(digits, evk, plan), plan)
                return self.pc.modq(c0p + d[0], bm), d[1]

            return jax.vmap(one)(c0b, c1b)

        return self._batched_fn(("galois_b", level), fn)

    def _hoist_batched_fn(self, level: int, n_rot: int, with_pt: bool,
                          digits_in: bool):
        def fn(plan, c0b, xb, perms, evk_all, pm_ext, pm_base, pm_ext_m):
            self._count_trace(
                ("hoisted_b", level, n_rot, with_pt, digits_in))

            def one(c0, x):
                digits = x if digits_in else self._modup(x, plan)
                return self._hoist_core(
                    plan, n_rot, with_pt, c0, digits, perms, evk_all,
                    pm_ext, pm_base, pm_ext_m,
                )

            return jax.vmap(one)(c0b, xb)

        return self._batched_fn(
            ("hoisted_b", level, n_rot, with_pt, digits_in), fn)

    def _multi_batched_fn(self, level: int, n: int):
        def fn(plan, c0s, digits, perms, evk_all):
            self._count_trace(("multi_hoisted_b", level, n))

            def one(c0s_1, digits_1):
                return self._multi_core(plan, n, c0s_1, digits_1,
                                        perms, evk_all)

            return jax.vmap(one, in_axes=(1, 1))(c0s, digits)

        return self._batched_fn(("multi_hoisted_b", level, n), fn)

    def _relin_batched_fn(self, level: int, digits_in: bool):
        def fn(plan, d0b, d1b, xb, evk):
            self._count_trace(("relin_b", level, digits_in))

            def one(d0, d1, x):
                digits = x if digits_in else self._modup(x, plan)
                return self._relin_core(plan, d0, d1, digits, evk)

            return jax.vmap(one)(d0b, d1b, xb)

        return self._batched_fn(("relin_b", level, digits_in), fn)

    def _multi_relin_batched_fn(self, level: int, n: int):
        def fn(plan, d0s, d1s, digits, evk):
            self._count_trace(("multi_relin_b", level, n))

            def one(d0s_1, d1s_1, digits_1):
                return self._multi_relin_core(plan, n, d0s_1, d1s_1,
                                              digits_1, evk)

            return jax.vmap(one, in_axes=(1, 1, 1))(d0s, d1s, digits)

        return self._batched_fn(("multi_relin_b", level, n), fn)

    def _modup_batched_fn(self, level: int):
        def fn(plan, ab):
            self._count_trace(("modup_b", level))
            return jax.vmap(lambda a: self._modup(a, plan))(ab)

        return self._batched_fn(("modup_b", level), fn)

    # ------------------------- public API ------------------------------
    def keyswitch(self, a, evk: EvalKey, level: int):
        """ModUp -> IP -> ModDown of poly ``a``: (d0, d1) under Q_level."""
        self._note_dispatch("keyswitch")
        self._note_keyswitch(self._plan(level))
        return self._ks_fn(level)(a, self.evk_tensor(evk, level))

    def apply_galois(self, c0, c1, galois: int, evk: EvalKey, level: int):
        """Fused rotate: eval-domain automorphism + keyswitch of c1."""
        self._note_dispatch("rotate")
        self._note_keyswitch(self._plan(level))
        self.counters.rotation += 1
        perm = self.perm_tensor([galois])[0]
        return self._galois_fn(level)(
            c0, c1, perm, self.evk_tensor(evk, level)
        )

    def modup(self, a, level: int):
        """Standalone ModUp of poly ``a`` -> (dnum, l_ext, N) digits.

        The runtime executor shares the result across all hoisted blocks
        anchored on the same ciphertext (cross-block double hoisting)."""
        self._note_dispatch("modup")
        plan = self._plan(level)
        self.counters.note_modup(plan.l, plan.l_ext, plan.group_sizes,
                                 plan.N)
        return self._modup_fn(level)(a)

    def hoisted_rotation_sum(self, c0, c1, galois_list: list[int],
                             evks: list[EvalKey], level: int,
                             pm_ext=None, pm_base=None, pm_ext_mont=None,
                             digits=None):
        """sum_r [pt_r *] Rot(ct, r): ONE ModUp, ONE (batched) ModDown.

        pm_ext/pm_base: (R, l_ext, N) / (R, l, N) PModUp'd plaintexts
        (uint64); pm_ext_mont: Montgomery uint32 form (pallas backend,
        which reads it INSTEAD of pm_ext — pm_ext may then be None).
        ``digits``: pre-computed ModUp digits from :meth:`modup` — the
        internal ModUp is skipped (bit-exact with the monolithic path).
        """
        self._note_dispatch("hoisted_rotation_sum")
        plan = self._plan(level)
        self._note_hoisted(plan, len(galois_list), digits is None)
        perms = self.perm_tensor(galois_list)
        evk_all = self.evk_group_tensor(evks, level)
        with_pt = pm_base is not None
        if digits is not None:
            fn = self._hoist_digits_fn(level, len(galois_list), with_pt)
            return fn(c0, digits, perms, evk_all, pm_ext, pm_base,
                      pm_ext_mont)
        fn = self._hoist_fn(level, len(galois_list), with_pt)
        return fn(c0, c1, perms, evk_all, pm_ext, pm_base, pm_ext_mont)

    def multi_hoisted_rotation_sum(self, c0s, digits_list, galois_list,
                                   evks, level: int):
        """sum_i Rot_{g_i}(ct_i) over DIFFERENT anchor ciphertexts with
        ONE ModDown (``runtime.lower.MultiHoistedStep``).

        ``c0s``/``digits_list``: per-term c0 polynomials and pre-computed
        ModUp digits (from :meth:`modup` — each anchor pays its own
        ModUp, shared with sibling hoisted blocks via the runtime's
        digits cache).  Per-term IPs accumulate in the extended basis;
        a single batched ModDown closes the sum — numerically close to,
        but not bit-identical with, per-rotation keyswitches (the
        approximate-FBC rounding of the merged ModDowns differs).
        """
        self._note_dispatch("multi_hoisted_rotation_sum")
        plan = self._plan(level)
        n = len(galois_list)
        c = self.counters
        c.note_ip(plan.dnum, plan.l_ext, plan.N, n)
        c.note_moddown(plan.l, plan.k, plan.N)
        c.keyswitch += n
        c.rotation += n
        perms = self.perm_tensor(galois_list)
        evk_all = self.evk_group_tensor(evks, level)
        return self._multi_fn(level, n)(
            jnp.stack(c0s), jnp.stack(digits_list), perms, evk_all
        )

    def relin(self, d0, d1, d2, evk: EvalKey, level: int, digits=None):
        """Relinearize a degree-2 ciphertext: (d0, d1) + KS(d2).

        The relin member of the keyswitch family: ModUp of the d2
        tensor-product component (skipped when pre-computed ``digits``
        from :meth:`modup` are passed — same digits-cache interface as
        the hoisted rotations), one IP against the mult key, one batched
        ModDown, and the base-domain folds into d0/d1 — all inside one
        cached jit plan.  Bit-exact with keyswitch-then-add.
        """
        self._note_dispatch("relin")
        plan = self._plan(level)
        self._note_relin(plan, digits is None)
        fn = self._relin_fn(level, digits is not None)
        x = digits if digits is not None else d2
        return fn(d0, d1, x, self.evk_tensor(evk, level))

    def multi_relin_sum(self, d0s, d1s, digits_list, evk: EvalKey,
                        level: int):
        """sum_i [(d0_i, d1_i) + KS(d2_i)] with ONE ModDown
        (``runtime.lower.MultiRelinStep``).

        ``digits_list``: per-term pre-computed ModUp digits of the d2
        components (from :meth:`modup` — each term pays its own ModUp;
        d2 tensors are fresh per CMult, so unlike rotation anchors they
        never share one).  Every term's IP against the SHARED mult key
        accumulates in the extended basis and a single batched ModDown
        closes the sum — numerically close to, but not bit-identical
        with, per-term relinearization (the approximate-FBC rounding of
        the merged ModDowns differs), exactly like
        :meth:`multi_hoisted_rotation_sum`.
        """
        self._note_dispatch("multi_relin_sum")
        plan = self._plan(level)
        n = len(digits_list)
        self._note_relin(plan, with_modup=False, n=n)
        return self._multi_relin_fn(level, n)(
            jnp.stack(d0s), jnp.stack(d1s), jnp.stack(digits_list),
            self.evk_tensor(evk, level),
        )

    # -------- batched public API (leading ct axis, jnp backend) --------
    def keyswitch_batched(self, ab, evk: EvalKey, level: int):
        """Batched keyswitch of (B, l, N) polys through ONE jit trace."""
        self._note_dispatch("keyswitch_batched")
        self._note_keyswitch(self._plan(level), m=int(ab.shape[0]))
        return self._ks_batched_fn(level)(ab, self.evk_tensor(evk, level))

    def apply_galois_batched(self, c0b, c1b, galois: int, evk: EvalKey,
                             level: int):
        self._note_dispatch("rotate_batched")
        self._note_keyswitch(self._plan(level), m=int(c0b.shape[0]))
        self.counters.rotation += int(c0b.shape[0])
        perm = self.perm_tensor([galois])[0]
        return self._galois_batched_fn(level)(
            c0b, c1b, perm, self.evk_tensor(evk, level)
        )

    def modup_batched(self, ab, level: int):
        self._note_dispatch("modup_batched")
        plan = self._plan(level)
        plan_sizes = plan.group_sizes
        self.counters.note_modup(plan.l, plan.l_ext, plan_sizes, plan.N,
                                 m=int(ab.shape[0]))
        return self._modup_batched_fn(level)(ab)

    def multi_hoisted_rotation_sum_batched(self, c0s, digits_list,
                                           galois_list, evks, level: int):
        """Batched multi-anchor accumulation: per-term (B, l, N) c0s and
        (B, dnum, l_ext, N) digits, vmapped over the ct axis."""
        self._note_dispatch("multi_hoisted_rotation_sum_batched")
        plan = self._plan(level)
        n = len(galois_list)
        m = int(c0s[0].shape[0])
        c = self.counters
        c.note_ip(plan.dnum, plan.l_ext, plan.N, n, m)
        c.note_moddown(plan.l, plan.k, plan.N, m)
        c.keyswitch += m * n
        c.rotation += m * n
        perms = self.perm_tensor(galois_list)
        evk_all = self.evk_group_tensor(evks, level)
        return self._multi_batched_fn(level, n)(
            jnp.stack(c0s), jnp.stack(digits_list), perms, evk_all
        )

    def relin_batched(self, d0b, d1b, d2b, evk: EvalKey, level: int,
                      digits=None):
        """Batched relinearization of (B, l, N) degree-2 components
        through ONE jit trace (``digits``: (B, dnum, l_ext, N))."""
        self._note_dispatch("relin_batched")
        plan = self._plan(level)
        self._note_relin(plan, digits is None, m=int(d0b.shape[0]))
        fn = self._relin_batched_fn(level, digits is not None)
        x = digits if digits is not None else d2b
        return fn(d0b, d1b, x, self.evk_tensor(evk, level))

    def multi_relin_sum_batched(self, d0s, d1s, digits_list,
                                evk: EvalKey, level: int):
        """Batched multi-relin accumulation: per-term (B, l, N) d0/d1
        and (B, dnum, l_ext, N) digits, vmapped over the ct axis."""
        self._note_dispatch("multi_relin_sum_batched")
        plan = self._plan(level)
        n = len(digits_list)
        self._note_relin(plan, with_modup=False, n=n,
                         m=int(d0s[0].shape[0]))
        return self._multi_relin_batched_fn(level, n)(
            jnp.stack(d0s), jnp.stack(d1s), jnp.stack(digits_list),
            self.evk_tensor(evk, level),
        )

    def hoisted_rotation_sum_batched(self, c0b, c1b, galois_list,
                                     evks, level: int, pm_ext=None,
                                     pm_base=None, pm_ext_mont=None,
                                     digits=None):
        """vmap over the ct axis: (B, l, N) c0/c1 (or (B, dnum, l_ext, N)
        pre-computed ``digits``), shared perm/evk/plaintext tensors."""
        self._note_dispatch("hoisted_rotation_sum_batched")
        plan = self._plan(level)
        self._note_hoisted(plan, len(galois_list), digits is None,
                           m=int(c0b.shape[0]))
        perms = self.perm_tensor(galois_list)
        evk_all = self.evk_group_tensor(evks, level)
        with_pt = pm_base is not None
        fn = self._hoist_batched_fn(level, len(galois_list), with_pt,
                                    digits is not None)
        x = digits if digits is not None else c1b
        return fn(c0b, x, perms, evk_all, pm_ext, pm_base, pm_ext_mont)
