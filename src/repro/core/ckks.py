"""RNS-CKKS scheme: encrypt/decrypt, EWOs, keyswitch, rotation, hoisting.

Ciphertext polynomials are (level+1, N) uint64 arrays in EVAL (NTT) domain.
ModUp/ModDown follow the paper's xPU pipeline (INTT -> BConv -> NTT).
The hoisted-rotation API implements "double hoisting" (Bossuat et al. [4]):
one ModUp per ciphertext, one ModDown per linear combination — the
communication-reduction primitive HERO maximizes.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core import poly
from repro.core.counters import OpCounters
from repro.core.encoding import Encoder
from repro.core.keys import EvalKey, KeyChain, sample_gaussian, to_rns
from repro.core.keyswitch import (
    KeyswitchEngine, _to_mont_host_rows, ext_rows,
)
from repro.core.params import CKKSParams
from repro.errors import (
    CorruptCiphertextError, LevelExhaustedError,
    ModulusChainMismatchError, ScaleDriftError,
)


@dataclasses.dataclass
class Ciphertext:
    c0: jnp.ndarray  # (level+1, N) eval domain
    c1: jnp.ndarray
    level: int
    scale: float

    @property
    def n_limbs(self) -> int:
        return self.level + 1


@dataclasses.dataclass
class Plaintext:
    m: jnp.ndarray  # (level+1, N) eval domain
    level: int
    scale: float


def tensor_product(a: Ciphertext, b: Ciphertext, mods,
                   pc: poly.PolyContext) -> tuple:
    """(d0, d1, d2) of the degree-2 ciphertext product, pre-relin.

    The single call site for the CMult tensor product: both the eager
    ``CKKSContext.multiply`` and the compiled runtime's ``RelinStep``/
    ``MultiRelinStep`` execution build their d-components here, so the
    relin keyswitch always sees identical operands.  Elementwise mod-q
    ops broadcast over an optional leading batch axis unchanged.
    """
    d0 = pc.mul(a.c0, b.c0, mods)
    d1 = pc.add(pc.mul(a.c0, b.c1, mods), pc.mul(a.c1, b.c0, mods), mods)
    d2 = pc.mul(a.c1, b.c1, mods)
    return d0, d1, d2


class CKKSContext:
    """Everything needed to run CKKS programs functionally.

    ``backend`` ("jnp" | "pallas") selects the keyswitch engine's
    numeric implementation; ``use_engine=False`` falls back to the seed
    per-digit/per-rotation loop path (kept for benchmarking and parity
    tests — both paths are bit-exact).
    """

    def __init__(self, params: CKKSParams, seed: int = 1234,
                 hamming_weight: int | None = None,
                 backend: str = "jnp", use_engine: bool = True):
        self.params = params
        self.pc = poly.PolyContext(params, backend=backend)
        self.encoder = Encoder(params)
        self.keys = KeyChain(
            params, self.pc, seed=seed, hamming_weight=hamming_weight
        )
        self.rng = np.random.default_rng(seed + 1)
        # Op counters (keyswitch/modup/moddown/ip/rotation invocations),
        # shared with the engine so both dispatch paths tally into one
        # place; runtime reports and fusion tests read the deltas.
        self.counters = OpCounters()
        self.engine = KeyswitchEngine(self.pc, counters=self.counters)
        self.use_engine = use_engine
        # (pt ids, level) -> (pts, pm_ext, pm_base, pm_ext_mont); the pts
        # tuple pins the objects so ids cannot be reused.  Bounded (FIFO
        # eviction): fresh plaintext sets must not accumulate forever.
        self._pm_stacks: dict[tuple, tuple] = {}
        self._pm_stacks_max = 32

    # ------------------------- helpers --------------------------------
    def chain(self, level: int) -> tuple[int, ...]:
        return self.params.q_chain(level)

    def ext_basis(self, level: int) -> tuple[int, ...]:
        return self.chain(level) + self.params.p_primes

    def _ext_rows(self, level: int) -> np.ndarray:
        """Rows of a full-basis evk active at ``level``."""
        return ext_rows(self.params, level)

    # ------------------------- encode / encrypt ------------------------
    def encode(self, z, level: int | None = None,
               scale: float | None = None) -> Plaintext:
        level = self.params.L if level is None else level
        scale = self.params.scale if scale is None else scale
        primes = self.chain(level)
        m = self.encoder.encode(np.asarray(z), scale, primes)
        m_eval = poly.ntt(jnp.asarray(m), primes, self.pc)
        return Plaintext(m=m_eval, level=level, scale=scale)

    def encrypt(self, z, level: int | None = None,
                scale: float | None = None) -> Ciphertext:
        pt = self.encode(z, level, scale)
        level = pt.level
        primes = self.chain(level)
        mods = self.pc.mods(primes)
        N = self.params.N
        a_rns = np.stack(
            [self.rng.integers(0, q, N, dtype=np.uint64) for q in primes]
        )
        a = poly.ntt(jnp.asarray(a_rns), primes, self.pc)
        e = poly.ntt(
            jnp.asarray(to_rns(sample_gaussian(self.rng, N), primes)),
            primes, self.pc,
        )
        s = self._sk_rows(level)
        b = self.pc.add(self.pc.sub(e, self.pc.mul(a, s, mods), mods), pt.m, mods)
        return Ciphertext(c0=b, c1=a, level=level, scale=pt.scale)

    def _sk_rows(self, level: int) -> jnp.ndarray:
        return self.keys.s_eval[: level + 1]

    def decrypt(self, ct: Ciphertext) -> np.ndarray:
        primes = self.chain(ct.level)
        mods = self.pc.mods(primes)
        m_eval = self.pc.add(
            ct.c0, self.pc.mul(ct.c1, self._sk_rows(ct.level), mods), mods
        )
        m_coeff = poly.intt(m_eval, primes, self.pc)
        return self.encoder.decode(np.asarray(m_coeff), ct.scale, primes)

    # ------------------------- guard checks ----------------------------
    def _require_same_level(self, a: Ciphertext, b: Ciphertext,
                            op: str) -> None:
        if a.level != b.level:
            raise ModulusChainMismatchError(
                f"{op}: operand levels disagree",
                hint="bring operands to a common level with level_down",
                lhs_level=a.level, rhs_level=b.level)

    def _require_pt_level(self, ct: Ciphertext, pt: Plaintext,
                          op: str) -> None:
        if pt.level < ct.level:
            raise ModulusChainMismatchError(
                f"{op}: plaintext encoded below the ciphertext level",
                hint="re-encode the plaintext at level >= ct.level",
                ct_level=ct.level, pt_level=pt.level)

    def check_ciphertext(self, ct: Ciphertext, where: str = "") -> None:
        """Ciphertext health guard: level sane, scale finite, limbs in
        range.  Raises a typed ``CiphertextError`` on the first violated
        invariant — the serving layer's opt-in per-request validator and
        the runtime executor's block-boundary checker both call this.

        The residue check runs as plain (eager) jnp reductions, so it
        never touches the engine's jit plan caches: turning validation
        on adds ZERO engine retraces (``engine.trace_counts`` is flat).
        """
        tag = f" at {where}" if where else ""
        if not 0 <= ct.level <= self.params.L:
            raise LevelExhaustedError(
                f"ciphertext level out of range{tag}",
                hint="bootstrap (or re-encrypt) before more rescales",
                level=ct.level, L=self.params.L)
        s = float(ct.scale)
        if not np.isfinite(s) or s <= 0.0:
            raise ScaleDriftError(
                f"ciphertext scale is not a positive finite float{tag}",
                hint="the producing op corrupted the scale trajectory",
                scale=ct.scale, level=ct.level)
        n = ct.level + 1
        for name, comp in (("c0", ct.c0), ("c1", ct.c1)):
            if comp.shape[-2] != n:
                raise ModulusChainMismatchError(
                    f"{name} carries {comp.shape[-2]} limbs but level "
                    f"{ct.level} needs {n}{tag}",
                    hint="ciphertext limbs and level drifted apart",
                    limbs=comp.shape[-2], level=ct.level)
        mods = self.pc.mods(self.chain(ct.level))[:, None]
        for name, comp in (("c0", ct.c0), ("c1", ct.c1)):
            if jnp.issubdtype(comp.dtype, jnp.floating):
                if bool(jnp.any(jnp.isnan(comp))):
                    raise CorruptCiphertextError(
                        f"NaN limb in {name}{tag}",
                        hint="a kernel produced NaN output",
                        component=name, level=ct.level)
                continue
            bad = int(jnp.sum(comp >= mods))
            if bad:
                raise CorruptCiphertextError(
                    f"{bad} residue(s) of {name} out of [0, q){tag}",
                    hint="upstream data corruption — do not decrypt; "
                         "re-encrypt and resubmit the request",
                    component=name, level=ct.level, bad_residues=bad)

    # ------------------------- EWOs ------------------------------------
    def add(self, a: Ciphertext, b: Ciphertext) -> Ciphertext:
        self._require_same_level(a, b, "add")
        mods = self.pc.mods(self.chain(a.level))
        return Ciphertext(
            self.pc.add(a.c0, b.c0, mods), self.pc.add(a.c1, b.c1, mods),
            a.level, a.scale,
        )

    def sub(self, a: Ciphertext, b: Ciphertext) -> Ciphertext:
        self._require_same_level(a, b, "sub")
        mods = self.pc.mods(self.chain(a.level))
        return Ciphertext(
            self.pc.sub(a.c0, b.c0, mods), self.pc.sub(a.c1, b.c1, mods),
            a.level, a.scale,
        )

    def pt_add(self, a: Ciphertext, pt: Plaintext) -> Ciphertext:
        self._require_pt_level(a, pt, "pt_add")
        mods = self.pc.mods(self.chain(a.level))
        return Ciphertext(
            self.pc.add(a.c0, pt.m[: a.n_limbs], mods), a.c1, a.level, a.scale
        )

    def pt_mul(self, a: Ciphertext, pt: Plaintext,
               rescale: bool = True) -> Ciphertext:
        self._require_pt_level(a, pt, "pt_mul")
        mods = self.pc.mods(self.chain(a.level))
        out = Ciphertext(
            self.pc.mul(a.c0, pt.m[: a.n_limbs], mods),
            self.pc.mul(a.c1, pt.m[: a.n_limbs], mods),
            a.level, a.scale * pt.scale,
        )
        return self.rescale(out) if rescale else out

    # ------------------------- level management ------------------------
    def rescale(self, ct: Ciphertext) -> Ciphertext:
        lvl = ct.level
        if lvl < 1:
            raise LevelExhaustedError(
                "rescale at level 0: the modulus chain is exhausted",
                hint="bootstrap the ciphertext (or recompile the program "
                     "with bootstrap insertion) before further mults",
                level=lvl)
        q_last = self.chain(lvl)[-1]
        c0 = poly.rescale(ct.c0, lvl, self.pc)
        c1 = poly.rescale(ct.c1, lvl, self.pc)
        return Ciphertext(c0, c1, lvl - 1, ct.scale / q_last)

    def level_down(self, ct: Ciphertext, target: int) -> Ciphertext:
        if not 0 <= target <= ct.level:
            raise ModulusChainMismatchError(
                "level_down target outside [0, ct.level]",
                hint="level_down only drops limbs; it cannot raise",
                target=target, level=ct.level)
        n = target + 1
        return Ciphertext(ct.c0[:n], ct.c1[:n], target, ct.scale)

    def mod_raise(self, ct: Ciphertext) -> Ciphertext:
        """Lift a level-0 ciphertext to the full chain (exact, coeffs < q0).

        The bootstrap boundary op: each component is brought to the
        coefficient domain, centered-lifted off the q0 basis, and re-NTT'd
        over the full chain — decrypting the result yields m + q0*I with
        |I| bounded by the secret's hamming weight.  The compiled runtime
        executes ``OpKind.MOD_RAISE`` nodes through this entry point.
        """
        from repro.core.encoding import centered_crt
        from repro.core.keys import to_rns

        p = self.params
        if ct.level != 0:
            raise ModulusChainMismatchError(
                "mod_raise expects a level-0 ciphertext",
                hint="consume the remaining levels (or level_down) first",
                level=ct.level)
        base = (p.q_primes[0],)
        full = p.q_chain(p.L)
        out = []
        for comp in (ct.c0, ct.c1):
            coeff = poly.intt(comp, base, self.pc)
            centered = centered_crt(np.asarray(coeff), base)
            lifted = to_rns(centered.astype(np.int64), full)
            out.append(poly.ntt(jnp.asarray(lifted), full, self.pc))
        return Ciphertext(out[0], out[1], p.L, ct.scale)

    # ------------------------- keyswitch core --------------------------
    # The batched jit engine (repro.core.keyswitch) is the default hot
    # path; the seed per-digit loop methods below are retained as the
    # bit-exact reference baseline (benchmarks + parity tests).
    def modup_digits(self, a: jnp.ndarray, level: int) -> list[jnp.ndarray]:
        """Decompose+ModUp a (level+1, N) poly to the extended basis."""
        groups = self.params.digit_groups(level)
        target = self.ext_basis(level)
        out = []
        row = 0
        for D in groups:
            digit = a[row : row + len(D)]
            out.append(
                poly.modup_digit(digit, D, target, self.pc, eval_domain=True)
            )
            row += len(D)
        return out

    def inner_product(self, digits: list[jnp.ndarray], evk: EvalKey,
                      level: int) -> tuple[jnp.ndarray, jnp.ndarray]:
        """IP over the extended basis: (sum_j d_j*evk_j0, sum_j d_j*evk_j1)."""
        rows = self._ext_rows(level)
        ext = self.ext_basis(level)
        mods = self.pc.mods(ext)
        acc0 = acc1 = None
        for j, d in enumerate(digits):
            k = evk.digits[j]
            t0 = self.pc.mul(d, k[0][rows], mods)
            t1 = self.pc.mul(d, k[1][rows], mods)
            acc0 = t0 if acc0 is None else self.pc.add(acc0, t0, mods)
            acc1 = t1 if acc1 is None else self.pc.add(acc1, t1, mods)
        return acc0, acc1

    def _note_seed_ks(self, level: int, n_ip: int = 1,
                      modups: int = 1) -> None:
        """Seed-path analogue of the engine's dispatch-time counting."""
        c = self.counters
        groups = tuple(len(D) for D in self.params.digit_groups(level))
        l, ext = level + 1, level + 1 + self.params.k
        N = self.params.N
        for _ in range(modups):
            c.note_modup(l, ext, groups, N)
        c.note_ip(len(groups), ext, N, n_ip)
        c.note_moddown(l, self.params.k, N)
        c.keyswitch += n_ip

    def keyswitch_seed(self, a: jnp.ndarray, evk: EvalKey,
                       level: int) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Seed per-digit keyswitch: ModUp -> IP -> ModDown loops."""
        self._note_seed_ks(level)
        digits = self.modup_digits(a, level)
        acc0, acc1 = self.inner_product(digits, evk, level)
        d0 = poly.moddown(acc0, level, self.pc)
        d1 = poly.moddown(acc1, level, self.pc)
        return d0, d1

    def keyswitch(self, a: jnp.ndarray, evk: EvalKey,
                  level: int) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Full keyswitch of poly ``a``: ModUp -> IP -> ModDown."""
        if self.use_engine:
            return self.engine.keyswitch(a, evk, level)
        return self.keyswitch_seed(a, evk, level)

    # ------------------------- mult / rotate ---------------------------
    def multiply(self, a: Ciphertext, b: Ciphertext,
                 rescale: bool = True) -> Ciphertext:
        """CMult: tensor product + relinearization of d2.

        The engine path dispatches the keyswitch-family ``relin`` entry
        point (ModUp -> IP -> ModDown -> base-domain folds, one cached
        jit plan); the seed path keeps the per-digit loops.  Both are
        bit-exact and tally identical ``OpCounters``.
        """
        self._require_same_level(a, b, "multiply")
        lvl = a.level
        mods = self.pc.mods(self.chain(lvl))
        d0, d1, d2 = tensor_product(a, b, mods, self.pc)
        if self.use_engine:
            c0, c1 = self.engine.relin(d0, d1, d2, self.keys.mult_key, lvl)
        else:
            self.counters.relin += 1
            e0, e1 = self.keyswitch_seed(d2, self.keys.mult_key, lvl)
            c0, c1 = self.pc.add(d0, e0, mods), self.pc.add(d1, e1, mods)
        out = Ciphertext(c0, c1, lvl, a.scale * b.scale)
        return self.rescale(out) if rescale else out

    def square(self, a: Ciphertext, rescale: bool = True) -> Ciphertext:
        return self.multiply(a, a, rescale=rescale)

    def double(self, ct: Ciphertext) -> Ciphertext:
        """2*ct without scale change (cheap: residues doubled mod q)."""
        mods = self.pc.mods(self.chain(ct.level))
        two = (mods * 0 + 2).astype(mods.dtype)
        return Ciphertext(
            self.pc.mul_scalar(ct.c0, two, mods),
            self.pc.mul_scalar(ct.c1, two, mods),
            ct.level, ct.scale,
        )

    def _apply_galois(self, ct: Ciphertext, galois: int,
                      evk: EvalKey) -> Ciphertext:
        lvl = ct.level
        if self.use_engine:
            c0, c1 = self.engine.apply_galois(ct.c0, ct.c1, galois, evk, lvl)
            return Ciphertext(c0, c1, lvl, ct.scale)
        primes = self.chain(lvl)
        mods = self.pc.mods(primes)
        self.counters.rotation += 1
        c0r = poly.automorphism(ct.c0, primes, galois, self.pc)
        c1r = poly.automorphism(ct.c1, primes, galois, self.pc)
        d0, d1 = self.keyswitch_seed(c1r, evk, lvl)
        return Ciphertext(
            self.pc.add(c0r, d0, mods), d1, lvl, ct.scale
        )

    def rotate(self, ct: Ciphertext, steps: int) -> Ciphertext:
        steps = steps % self.params.num_slots
        if steps == 0:
            return ct
        g = self.pc.rns.galois_for_rotation(steps)
        return self._apply_galois(ct, g, self.keys.rot_key(steps))

    def conjugate(self, ct: Ciphertext) -> Ciphertext:
        g = self.pc.rns.galois_conjugate()
        return self._apply_galois(ct, g, self.keys.conj_key)

    # ------------------------- hoisted rotations -----------------------
    def hoist_digits(self, ct: Ciphertext) -> jnp.ndarray | None:
        """ModUp of ct.c1 for reuse across hoisted blocks (engine only).

        The compiled runtime (``repro.runtime``) calls this once per
        anchor ciphertext and feeds the digits to every hoisted block it
        anchors — cross-block double hoisting.  Returns None on the seed
        path (which has no digits-in entry point)."""
        if not self.use_engine:
            return None
        return self.engine.modup(ct.c1, ct.level)

    def hoisted_rotation_sum(
        self, ct: Ciphertext, steps_list: list[int],
        pts: list[Plaintext] | None = None, rescale: bool = True,
        digits: jnp.ndarray | None = None,
    ) -> Ciphertext:
        """sum_r pt_r * Rot(ct, r) with ONE ModUp and ONE ModDown.

        This is the hoisting primitive of Fig. 2(c): the ModUp of c1 is
        shared across all rotations; per-rotation IP results (and PModUp'd
        plaintext muls — Eq. (1)) are accumulated in the extended basis;
        a single ModDown closes the block.  ``digits`` (from
        :meth:`hoist_digits`) skips even that ModUp — blocks sharing an
        anchor ciphertext share one ModUp program-wide.

        Step-0 terms never touch the keyswitch machinery: Rot_0 is the
        identity, so they contribute a plain (pt-mul'd) base-domain add —
        one IP fewer per block, no identity-keyswitch noise, and the
        same arithmetic whether the term appears alone (``ctx.pt_mul``)
        or inside a block (which is what keeps the compiled runtime's
        lowering bit-exact regardless of how the 0th diagonal lands).
        """
        steps_norm = [s % self.params.num_slots for s in steps_list]
        nz = [i for i, s in enumerate(steps_norm) if s != 0]
        out = None
        if nz:
            nz_steps = [steps_norm[i] for i in nz]
            nz_pts = [pts[i] for i in nz] if pts is not None else None
            out = self._hoisted_block(ct, nz_steps, nz_pts, digits)
        out = self.add_zero_step_terms(out, ct, steps_norm, pts)
        if pts is not None and rescale:
            out = self.rescale(out)
        return out

    def add_zero_step_terms(self, out, ct: Ciphertext, steps_norm, pts):
        """Fold the identity (step-0) terms of a hoisted block into
        ``out`` as plain base-domain EWOs.  Shared by the eager primitive
        and the runtime's batched mirror (EWOs broadcast over a leading
        ct axis) so the two step-0 paths cannot drift apart."""
        for i, s in enumerate(steps_norm):
            if s != 0:
                continue
            term = (self.pt_mul(ct, pts[i], rescale=False)
                    if pts is not None else ct)
            out = term if out is None else self.add(out, term)
        return out

    def _hoisted_block(
        self, ct: Ciphertext, steps_list: list[int],
        pts: list[Plaintext] | None, digits: jnp.ndarray | None,
    ) -> Ciphertext:
        """The keyswitch part of a hoisted block (nonzero steps only)."""
        lvl = ct.level
        if self.use_engine:
            gs = [self.pc.rns.galois_for_rotation(s) for s in steps_list]
            keys = [self.keys.rot_key(s) for s in steps_list]
            pm_ext = pm_base = pm_ext_m = None
            if pts is not None:
                assert all(pt.level == lvl for pt in pts)
                pm_ext, pm_base, pm_ext_m = self._pm_stack(tuple(pts), lvl)
            c0, c1 = self.engine.hoisted_rotation_sum(
                ct.c0, ct.c1, gs, keys, lvl, pm_ext, pm_base, pm_ext_m,
                digits=digits,
            )
            out_scale = ct.scale * (pts[0].scale if pts is not None else 1.0)
            return Ciphertext(c0, c1, lvl, out_scale)
        assert digits is None, "digits sharing requires the engine path"
        return self._hoisted_rotation_sum_seed(ct, steps_list, pts,
                                               rescale=False)

    def _hoisted_rotation_sum_seed(
        self, ct: Ciphertext, steps_list: list[int],
        pts: list[Plaintext] | None = None, rescale: bool = True,
    ) -> Ciphertext:
        """Seed path: per-rotation automorphism/IP loops (reference)."""
        lvl = ct.level
        self._note_seed_ks(lvl, n_ip=len(steps_list))
        self.counters.rotation += len(steps_list)
        self.counters.hoisted_blocks += 1
        base = self.chain(lvl)
        ext = self.ext_basis(lvl)
        base_mods = self.pc.mods(base)
        ext_mods = self.pc.mods(ext)
        digits = self.modup_digits(ct.c1, lvl)

        pt_ms = None
        if pts is not None:
            pt_ms = []
            for pt in pts:
                assert pt.level == lvl
                pt_ms.append(pt)

        acc0e = acc1e = None
        base0 = None
        for i, steps in enumerate(steps_list):
            steps = steps % self.params.num_slots
            g = self.pc.rns.galois_for_rotation(steps)
            key = self.keys.rot_key(steps)
            # sigma_r commutes with ModUp (coefficient-wise BConv).
            dig_r = [
                poly.automorphism(d, ext, g, self.pc) for d in digits
            ]
            ks0, ks1 = self.inner_product(dig_r, key, lvl)
            c0r = poly.automorphism(ct.c0, base, g, self.pc)
            if pt_ms is not None:
                pm_ext = self._pmodup(pt_ms[i], lvl)
                ks0 = self.pc.mul(ks0, pm_ext, ext_mods)
                ks1 = self.pc.mul(ks1, pm_ext, ext_mods)
                c0r = self.pc.mul(c0r, pt_ms[i].m[: lvl + 1], base_mods)
            acc0e = ks0 if acc0e is None else self.pc.add(acc0e, ks0, ext_mods)
            acc1e = ks1 if acc1e is None else self.pc.add(acc1e, ks1, ext_mods)
            base0 = c0r if base0 is None else self.pc.add(base0, c0r, base_mods)

        d0 = poly.moddown(acc0e, lvl, self.pc)
        d1 = poly.moddown(acc1e, lvl, self.pc)
        out_scale = ct.scale * (pts[0].scale if pts is not None else 1.0)
        out = Ciphertext(
            self.pc.add(base0, d0, base_mods), d1, lvl, out_scale
        )
        if pts is not None and rescale:
            out = self.rescale(out)
        return out

    def _pmodup(self, pt: Plaintext, level: int) -> jnp.ndarray:
        """PModUp (Eq. (1)): EXACT lift of a plaintext to the extended basis.

        Unlike ciphertext ModUp, the lift must be exact (centered CRT):
        the approximate-FBC +k*Q error would multiply the keyswitch noise
        (which exceeds P/k) and destroy the message — this is why the paper
        cites the dedicated PModUp of MAD [1].  Plaintext coefficients are
        small, so the exact lift is just a centered lift + reduction.

        The centered lift reduces via a vectorized object-array ``%``
        (not a per-coefficient Python loop), and the result is cached on
        the plaintext per level — hoisted blocks reuse the same pt set.
        """
        cache = getattr(pt, "_pmodup_cache", None)
        if cache is None:
            cache = pt._pmodup_cache = {}
        if level in cache:
            return cache[level]
        from repro.core.encoding import centered_crt

        base = self.chain(level)
        ext = self.ext_basis(level)
        coeff = poly.intt(pt.m[: level + 1], base, self.pc)
        centered = centered_crt(np.asarray(coeff), base)
        new = tuple(p for p in ext if p not in base)
        lifted = np.stack(
            [(centered % q).astype(np.uint64) for q in new]
        )
        conv_eval = poly.ntt(jnp.asarray(lifted), new, self.pc)
        out = jnp.concatenate([pt.m[: level + 1], conv_eval], axis=0)
        cache[level] = out
        return out

    def _pm_stack(self, pts: tuple[Plaintext, ...], level: int):
        """Stacked hoisted-block plaintext tensors, cached per (pts, level)
        like the engine's evk group tensors.  The uint64 extended stack is
        only built for the jnp backend (the pallas fused-IP kernel reads
        the Montgomery form instead)."""
        key = (tuple(id(pt) for pt in pts), level)
        if key not in self._pm_stacks:
            pallas = self.pc.backend == "pallas"
            pm_ext = (None if pallas else
                      jnp.stack([self._pmodup(pt, level) for pt in pts]))
            pm_base = jnp.stack([pt.m[: level + 1] for pt in pts])
            pm_ext_m = (jnp.stack(
                [self._pmodup_mont(pt, level) for pt in pts]
            ) if pallas else None)
            while len(self._pm_stacks) >= self._pm_stacks_max:
                self._pm_stacks.pop(next(iter(self._pm_stacks)))
            self._pm_stacks[key] = (pts, pm_ext, pm_base, pm_ext_m)
        return self._pm_stacks[key][1:]

    def _pmodup_mont(self, pt: Plaintext, level: int) -> jnp.ndarray:
        """Montgomery uint32 form of ``_pmodup`` (pallas fused-IP PMul),
        cached alongside the uint64 lift."""
        cache = getattr(pt, "_pmodup_cache", None)
        if cache is None:
            cache = pt._pmodup_cache = {}
        key = (level, "mont")
        if key not in cache:
            pm = np.asarray(self._pmodup(pt, level))
            q = np.array(self.ext_basis(level), dtype=np.uint64)
            cache[key] = jnp.asarray(_to_mont_host_rows(pm, q))
        return cache[key]
