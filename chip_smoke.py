#!/usr/bin/env python3
"""Smoke run of the CKKS main path on one TPU, at the paper's ring.

    python chip_smoke.py

Runs in ONE process on one chip (it starts no child process; the host
reference runs in a thread) and drives the system through its user
entry points: trace -> compile -> execute -> serve, with every
keyswitch kernel Mosaic-compiled (``backend="pallas"``, interpret mode
off).  The references are the exact uint64 jnp backend, on the chip for
one hoisted-rotation block and on the host CPU (where 64-bit division
is native and jnp-backend plans compile in seconds, not minutes) for
whole programs.  Phases:

  (a) environment: a TPU must be attached — there is no CPU fallback,
      the script exits non-zero without printing a result.  The pallas
      engine must report ``interpret=False``, and so must every
      ``engine.kernel_dispatch`` event of the run.
  (b) packed logistic regression at ``PAPER_PARAMS`` (logN=16, L=35,
      alpha=12, k=12, dnum=3), batch 4: a banded 8-diagonal
      ``matvec_bsgs`` (bs=4), a bias ``pt_add``, and the degree-15
      ``sigmoid4`` by ``eval_chebyshev_bsgs``, built from its diagonals
      (a dense 2^15 x 2^15 matrix cannot be allocated).  Traced with
      ``TraceContext``, built with ``compile_program`` (fusion off, and
      on), run with ``ProgramExecutor.run_batched`` on the chip.  One
      hoisted block is bit-identical on the chip's pallas and jnp
      backends and on the host; the decrypt error must equal the CPU's
      (``LOGREG_CPU_ERR``); the first request's output of each program
      is bit-identical to the host reference.
  (c) a short seeded Poisson trace through ``serve.FHEServer`` for one
      tenant: every request completes, bit-identical to a direct
      ``run_batched`` of its ciphertexts under the tenant's keys.
  (d) ``Bootstrapper.compile()`` at the ``bench_bootstrap --smoke``
      ring, on the chip and on the host reference: bit-identical.

Every phase runs; any failed check exits non-zero.  Earlier lines
report set-up, compile (first run, jit tracing and compilation
included) and steady (second run) seconds, each ended by
``block_until_ready``, the number and seconds of XLA compiles, and the
device's ``peak_bytes_in_use`` — one smoke run, not a benchmark.  The
last line is ``{"ok": true, "device": {...}}``.

The persistent compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` when
set, else ``<checkout>/.jax_cache`` (``repro.compile_cache``).
"""
from __future__ import annotations

import json
import os
import pathlib
import sys
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
SEED = 0
BATCH = 4

# Phase (d): the bench_bootstrap --smoke ring and configuration.
# BOOT_TEST_PARAMS (logN=11, L=14, 2^25 scale on 30-bit words) cannot
# bootstrap: its chain cannot hold this configuration, and the shallowest
# one (1 group, mod_K=1, degree 7) ends at scale 2^10 with a max error of
# 2.5e27 (CPU; the command is in PERF.md, Open questions).
BOOT_PARAMS = dict(logN=8, L=19, alpha=4, k=4, q_bits=29, scale_bits=29,
                   q0_bits=30)
BOOT = dict(n_groups=2, mod_K=3, cheb_degree=27)
# Max slot error of (b)'s batch against the numpy reference, as the same
# program, seed and batch give it with the jnp backend on a CPU (and as
# a v5e reproduced it).  Residues are exact integers, so any wrong
# residue moves it: the chip must reproduce it.  It is large because of
# the parameter set's keyswitch noise (ROADMAP B4), not the device.
LOGREG_CPU_ERR = 5975325.527494363


class NoAccelerator(RuntimeError):
    pass


def log(tag: str, **fields) -> None:
    items = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{tag}] {items}", flush=True)


def device_info() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def require_tpu() -> dict:
    info = device_info()
    if info["platform"] != "tpu":
        raise NoAccelerator(f"no TPU attached (JAX platform "
                            f"{info['platform']!r}); this smoke has no "
                            f"CPU fallback")
    return info


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _block(cts):
    import jax

    jax.block_until_ready([(c.c0, c.c1) for c in cts])
    return cts


def _timed(fn):
    t = time.perf_counter()
    out = fn()
    if isinstance(out, list):
        _block(out)
    else:
        _block([out])
    return out, time.perf_counter() - t


def _same(a, b) -> bool:
    return all(np.array_equal(np.asarray(x.c0), np.asarray(y.c0))
               and np.array_equal(np.asarray(x.c1), np.asarray(y.c1))
               and x.level == y.level and x.scale == y.scale
               for x, y in zip(a, b, strict=True))


def dispatch_events() -> list[dict]:
    """Every ``engine.kernel_dispatch`` event the obs tracer holds."""
    from repro import obs

    evs = [a for n, _, _, a in obs.TRACER.instants
           if n == "engine.kernel_dispatch"]
    for sp in obs.TRACER.spans():
        evs += [a for n, _, a in sp.events if n == "engine.kernel_dispatch"]
    return evs


class CompileLog:
    """XLA backend compiles (persistent-cache misses), counted through
    ``jax.monitoring`` and logged per phase."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.t = time.perf_counter()
        self.by_name: dict[str, list] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, fun_name: str = "?",
            **_) -> None:
        if event == self.EVENT:
            n_s = self.by_name.setdefault(fun_name, [0, 0.0])
            n_s[0] += 1
            n_s[1] += duration

    def log(self, phase: str) -> None:
        """Wall seconds since the last call, compiles and their seconds,
        and the five programs that took longest to compile."""
        now = time.perf_counter()
        top = sorted(self.by_name.items(), key=lambda kv: -kv[1][1])[:5]
        log("a", phase=phase, wall_s=now - self.t,
            xla_compiles=sum(n for n, _ in self.by_name.values()),
            xla_compile_s=sum(s for _, s in self.by_name.values()),
            top=[(k, n, round(s, 1)) for k, (n, s) in top])
        self.by_name, self.t = {}, now


def check_compiled(ctx, interpret: bool) -> None:
    """The engine and every dispatch event agree on interpret mode."""
    assert ctx.engine.backend == "pallas"
    assert ctx.engine.interpret is interpret, ctx.engine.interpret
    evs = [e for e in dispatch_events() if e["backend"] == "pallas"]
    assert evs, "no pallas kernel dispatch was recorded"
    bad = [e for e in evs if e["interpret"] is not interpret]
    assert not bad, f"{len(bad)} dispatches ran with interpret != " \
                    f"{interpret}: {bad[:3]}"


def on_host():
    """Context placing new arrays and computations on the host CPU."""
    import jax

    return jax.default_device(jax.devices("cpu")[0])


def host_copy(x):
    import jax

    return jax.device_put(x, jax.devices("cpu")[0])


def host_ct(ct):
    from repro.core.ckks import Ciphertext

    return Ciphertext(host_copy(ct.c0), host_copy(ct.c1), ct.level, ct.scale)


def host_keys(keys, pc):
    """A host copy of a key chain: its secret, every key generated so
    far, and its random generator's state, computing with the host's
    ``pc``.  Keys generated later are random draws from that state, so
    the copy generates the same keys as the original when asked for
    them in the same order."""
    import copy

    from repro.core.keys import EvalKey

    def put(key):
        return None if key is None else EvalKey(
            [host_copy(d) for d in key.digits])

    out = copy.copy(keys)
    out.pc = pc
    out.rng = copy.deepcopy(keys.rng)
    out.s_eval = host_copy(keys.s_eval)
    out._rot_keys = {s: put(k) for s, k in keys._rot_keys.items()}
    out._mult_key = put(keys._mult_key)
    out._conj_key = put(keys._conj_key)
    return out


def host_context(ctx):
    """A jnp-backend context on the host CPU with a copy of ``ctx``'s
    key chain: the exact uint64 reference, computed where 64-bit
    integer division is native."""
    from repro.core.ckks import CKKSContext

    with on_host():
        hctx = CKKSContext(ctx.params, backend="jnp")
        hctx.keys = host_keys(ctx.keys, hctx.pc)
    return hctx


def host_run(hctx, prog, inputs: dict):
    """Run ``prog`` unbatched on the host reference context."""
    from repro.runtime import ProgramExecutor

    with on_host():
        res = ProgramExecutor(hctx).run(
            prog, {tag: host_ct(ct) for tag, ct in inputs.items()})
        return _block(list(res.outputs.values()))


class HostReference:
    """Replays the chip's programs on a host reference context, in one
    worker thread, while the chip runs; ``result`` waits for a job.

    Built before the chip generates any key, the host context's key
    chain replays the chip's: the same programs ask for the same keys in
    the same order, so the host generates the same keys itself and the
    chip never waits for the host."""

    def __init__(self, ctx):
        self.hctx = host_context(ctx)
        self.pool = ThreadPoolExecutor(max_workers=1,
                                       thread_name_prefix="host-ref")
        self.jobs = {}

    def submit(self, name: str, job) -> None:
        """Queue ``job(hctx)``, run under ``on_host``."""
        self.jobs[name] = self.pool.submit(self._run, job)

    def _run(self, job):
        t = time.perf_counter()
        with on_host():
            out = job(self.hctx)
            _block([out])
        return out, time.perf_counter() - t

    def result(self, name: str):
        """(output, seconds) of job ``name``, once it is done."""
        return self.jobs[name].result()

    def close(self) -> None:
        self.pool.shutdown()


class BandedLogreg:
    """``workloads.models.logreg`` built from its diagonals.

    Same seeded draws as ``logreg(nh, seed)`` (8 diagonals, rows scaled
    to inf-norm ``gain``, bias, sigmoid(4t) at degree 15) and the same
    op sequence as ``Dense.apply``, without the dense (nh, nh) matrix."""

    def __init__(self, nh: int, seed: int, n_diags: int = 8, bs: int = 4,
                 gain: float = 0.8, degree: int = 15):
        from repro.workloads.models import sigmoid4

        rng = np.random.default_rng(seed)
        vals = [rng.uniform(-1.0, 1.0, nh) for _ in range(n_diags)]
        rowsum = np.sum(np.abs(vals), axis=0)
        self.diags = {d: v * gain / rowsum for d, v in enumerate(vals)}
        self.bias = rng.uniform(-0.1, 0.1, nh)
        self.act = sigmoid4(degree)
        self.bs = bs
        self.nh = nh

    def apply(self, ctx, ct):
        from repro.core import linear
        from repro.core.polyeval import eval_chebyshev_bsgs

        out = linear.matvec_bsgs(ctx, ct, self.diags, bs=self.bs)
        out = ctx.pt_add(out, ctx.encode(self.bias, level=out.level,
                                         scale=out.scale))
        return eval_chebyshev_bsgs(ctx, out, self.act.coeffs)

    def reference(self, x: np.ndarray) -> np.ndarray:
        y = sum(v * np.roll(x, -d) for d, v in self.diags.items())
        return self.act.fn(y + self.bias)

    def sample(self, rng) -> np.ndarray:
        return rng.uniform(-1.0, 1.0, self.nh)

    def first_block_sum(self, ctx, ct):
        """The first baby-step hoisted block of ``apply`` (giant step 0,
        so the diagonals are not rolled), by ``hoisted_rotation_sum``."""
        steps = [d for d in sorted(self.diags) if d < self.bs]
        pts = [ctx.encode(self.diags[d], level=ct.level) for d in steps]
        return ctx.hoisted_rotation_sum(ct, steps, pts, rescale=False)


def phase_logreg(params, batch: int, seed: int, interpret: bool,
                 cpu_err: float | None):
    """(b), exact program on the chip; its decrypt error must equal the
    CPU's (``cpu_err``).  The host reference starts on the first
    request's exact and fused programs and one hoisted block, checked
    by :func:`check_host`.  Returns what the later phases reuse."""
    from repro.core.ckks import CKKSContext
    from repro.runtime import ProgramExecutor, TraceContext, compile_program

    t = time.perf_counter()
    model = BandedLogreg(params.num_slots, seed)
    ctx = CKKSContext(params, seed=seed, backend="pallas")
    rng = np.random.default_rng(seed)
    xs = [model.sample(rng) for _ in range(batch)]
    cts = _block([ctx.encrypt(x) for x in xs])
    host = HostReference(ctx)
    log("b", setup_s=time.perf_counter() - t)

    t = time.perf_counter()
    tc = TraceContext(params)
    tc.output(model.apply(tc, tc.input("x")), "y")
    progs = {"exact": compile_program(tc),
             "fused": compile_program(tc, fusion=True)}
    log("b", trace_lower_s=time.perf_counter() - t,
        **{f"{k}_steps": len(v.steps) for k, v in progs.items()})

    hct = host_ct(cts[0])
    for name, prog in progs.items():
        host.submit(name, lambda h, prog=prog: host_run(h, prog,
                                                        {"x": hct})[0])
    host.submit("block", lambda h: model.first_block_sum(h, hct))

    ex = ProgramExecutor(ctx)

    def run():
        return ex.run_batched(progs["exact"], {"x": cts})["y"]
    out, cold = _timed(run)
    again, steady = _timed(run)
    assert _same(again, out), "two runs of one program differ"
    log("b", backend="pallas", program="exact", batch=batch, compile_s=cold,
        steady_s=steady, peak_bytes=peak_bytes())
    check_compiled(ctx, interpret)

    err = max(float(np.abs(ctx.decrypt(o).real - model.reference(x)).max())
              for o, x in zip(out, xs))
    log("b", max_err=err, cpu_err=cpu_err)
    if cpu_err is not None:
        assert abs(err - cpu_err) <= 1e-9 * cpu_err, \
            f"decrypt error {err} differs from the CPU's {cpu_err}"
    block, t_block = _timed(lambda: model.first_block_sum(ctx, cts[0]))
    log("b", program="hoisted_block", s=t_block)
    return ctx, host, progs, model, cts, xs, {"exact": out[0],
                                              "block": block}


def phase_fused(ctx, prog, model, cts, xs, interpret: bool):
    """(b), fused program (HERO fusion on) on the chip.  Returns the
    first request's output (for the host check)."""
    from repro.runtime import ProgramExecutor

    out, cold = _timed(
        lambda: ProgramExecutor(ctx).run_batched(prog, {"x": cts})["y"])
    err = max(float(np.abs(ctx.decrypt(o).real - model.reference(x)).max())
              for o, x in zip(out, xs))
    log("b", backend="pallas", program="fused", batch=len(cts),
        compile_s=cold, max_err=err, peak_bytes=peak_bytes())
    check_compiled(ctx, interpret)
    return out[0]


def check_host(ctx, host, model, x, chip: dict) -> None:
    """(b): each chip output for the first request is bit-identical to
    the host reference's, and decrypts to the same error."""
    for name, out in chip.items():
        ref, s = host.result(name)
        assert _same([out], [ref]), f"{name}: chip and host differ"
        fields = {}
        if name != "block":
            with on_host():
                fields["host_err_ct0"] = float(np.abs(
                    host.hctx.decrypt(ref).real - model.reference(x)).max())
            fields["err_ct0"] = float(np.abs(
                ctx.decrypt(out).real - model.reference(x)).max())
            assert fields["err_ct0"] == fields["host_err_ct0"]
        log("b", backend="jnp@host", program=name, batch=1, host_s=s,
            chip_vs_host="bit-identical", **fields)
    host.close()


def phase_serve(ctx, program, model, batch: int, seed: int,
                n_requests: int, interpret: bool):
    """(c): a seeded trace for one tenant through ``FHEServer``; every
    request completes, and each output is bit-identical to a direct
    ``run_batched`` of the same ciphertexts under the tenant's keys."""
    from repro.runtime import ProgramExecutor
    from repro.serve import FHEServer, TenantRegistry, poisson_trace

    t = time.perf_counter()
    server = FHEServer(ctx, max_batch=batch,
                       registry=TenantRegistry(ctx, base_seed=seed + 1000))
    server.register_program("logreg", program)
    trace = poisson_trace(rate_rps=2.0, n=n_requests, tenants=["t0"],
                          programs=["logreg"], seed=seed)
    rng = np.random.default_rng(seed + 1)
    xs: list[np.ndarray] = []
    sent = []

    def inputs_for(arrival):
        xs.append(model.sample(rng))
        sent.append(ctx.encrypt(xs[-1]))
        return {"x": sent[-1]}

    with server.registry.lease("t0"):
        warm = {"x": ctx.encrypt(model.sample(rng))}
    server.warmup("t0", "logreg", warm)
    log("c", setup_s=time.perf_counter() - t)

    t = time.perf_counter()
    rep = server.run_trace(trace, inputs_for)
    wall = time.perf_counter() - t
    log("c", requests=n_requests, completed=rep.completed,
        batches=rep.batches, wall_s=wall, virtual_span_s=rep.span_s,
        peak_bytes=peak_bytes())
    assert rep.submitted == rep.completed == n_requests == len(sent), rep
    assert not (rep.failed or rep.shed or rep.rejected), rep
    assert sorted(server.outcomes) == list(range(n_requests))
    assert set(server.outcomes.values()) == {"completed"}

    with server.registry.lease("t0"):
        ex = ProgramExecutor(ctx)
        for lo in range(0, n_requests, batch):
            group = sent[lo : lo + batch]
            pad = group + [group[-1]] * (batch - len(group))
            direct = ex.run_batched(program, {"x": pad})["y"][: len(group)]
            served = [server.outputs[rid]["y"]
                      for rid in range(lo, lo + len(group))]
            assert _same(served, direct), "served != direct execution"
        err = max(float(np.abs(ctx.decrypt(server.outputs[rid]["y"]).real
                               - model.reference(x)).max())
                  for rid, x in enumerate(xs))
    log("c", served_vs_direct="bit-identical", max_err=err)
    check_compiled(ctx, interpret)


def phase_bootstrap(params, seed: int, boot: dict, interpret: bool):
    """(d): the compiled bootstrap on the chip vs the host reference."""
    from repro.core.bootstrap import Bootstrapper
    from repro.core.ckks import CKKSContext
    from repro.runtime import ProgramExecutor

    t = time.perf_counter()
    ctx = CKKSContext(params, seed=seed, backend="pallas", hamming_weight=8)
    btp = Bootstrapper(ctx, **boot)
    rng = np.random.default_rng(seed)
    nh = params.num_slots
    z = (rng.normal(size=nh) + 1j * rng.normal(size=nh)) * 0.01
    ct0 = _block([ctx.encrypt(z, level=0)])[0]
    prog = btp.compile(input_scale=ct0.scale)
    log("d", setup_s=time.perf_counter() - t, steps=len(prog.steps))

    ex = ProgramExecutor(ctx)

    def run():
        return ex.run(prog, {"ct": ct0})["out"]
    out, cold = _timed(run)
    again, steady = _timed(run)
    assert _same([again], [out])
    log("d", backend="pallas", compile_s=cold, steady_s=steady,
        peak_bytes=peak_bytes())
    check_compiled(ctx, interpret)

    t = time.perf_counter()
    hctx = host_context(ctx)
    ref = host_run(hctx, prog, {"ct": ct0})
    assert _same([out], ref), "bootstrap: chip and host jnp differ"
    err = float(np.abs(ctx.decrypt(out) - z).max())
    with on_host():
        host_err = float(np.abs(hctx.decrypt(ref[0]) - z).max())
    log("d", backend="jnp@host", s=time.perf_counter() - t,
        chip_vs_host="bit-identical", level=out.level, max_err=err,
        host_err=host_err)
    assert err <= host_err, (err, host_err)


def main() -> int:
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: run from a checkout of the repository "
              "(src/repro not found next to this script)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro import compile_cache, obs

    cache = compile_cache.enable(ROOT)
    try:
        dev = require_tpu()
    except NoAccelerator as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    from repro.core.params import PAPER_PARAMS, CKKSParams

    log("a", **dev, compile_cache=cache)
    obs.enable()
    compiles = CompileLog()
    t0 = time.perf_counter()
    ctx, host, progs, model, cts, xs, chip = phase_logreg(
        PAPER_PARAMS, BATCH, SEED, interpret=False, cpu_err=LOGREG_CPU_ERR)
    compiles.log("b_exact")
    chip["fused"] = phase_fused(ctx, progs["fused"], model, cts, xs,
                                interpret=False)
    compiles.log("b_fused")
    phase_serve(ctx, progs["exact"], model, BATCH, SEED, n_requests=6,
                interpret=False)
    compiles.log("c_serve")
    phase_bootstrap(CKKSParams(**BOOT_PARAMS), SEED, BOOT, interpret=False)
    compiles.log("d_bootstrap")
    check_host(ctx, host, model, xs[0], chip)
    compiles.log("b_host")
    assert "repro.launch.dryrun" not in sys.modules
    log("a", total_s=time.perf_counter() - t0, peak_bytes=peak_bytes())
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except BaseException:
        traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        # exit now: a failed phase must not wait for the host thread
        os._exit(1)
    sys.exit(rc)
