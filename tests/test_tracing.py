"""Tracing from inside the program: spans on the profiler's clock, named
keyswitch plans and phase scopes, and set-up counters (``repro.obs``).

  * each jitted keyswitch plan compiles under its stable name
    ``ks_<kind>_L<level>`` and carries the phase scopes (``ks.modup``,
    ``ks.ip``, ``ks.moddown``, ``ks.automorph``) in its op names;
  * an enabled ``obs.span`` enters a ``jax.profiler.TraceAnnotation``,
    a disabled one is the ``NULL_SPAN`` singleton;
  * the set-up counters count a persistent-cache miss on a first
    compile and a hit, with no miss, on the same compile after
    ``jax.clear_caches()``; nested intervals count once;
  * two fresh processes name every plan the same, and the second finds
    every compile of the first in the persistent cache.
"""
import json
import os
import re
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.ckks import CKKSContext
from repro.core.keyswitch import plan_name
from repro.core.params import CKKSParams
from repro.obs.setup import SetupCounters, self_seconds
from repro.obs.tracer import NULL_SPAN, Tracer

ALL_PHASES = {"ks.modup", "ks.ip", "ks.moddown"}


@pytest.fixture(scope="module")
def tctx():
    params = CKKSParams(logN=8, L=3, alpha=2, k=2, q_bits=29,
                        scale_bits=29)
    return CKKSContext(params, seed=23)


def _plan_case(ctx, kind):
    """The cached jitted plan of ``kind`` at the top level, with
    arguments of the right shapes: ``(plan, args, scopes it has)``."""
    eng, lvl = ctx.engine, ctx.params.L
    rng = np.random.default_rng(0)
    ct = ctx.encrypt(rng.normal(size=ctx.params.num_slots))
    keys = [ctx.keys.rot_key(s) for s in (1, 3)]
    gs = [ctx.pc.rns.galois_for_rotation(s) for s in (1, 3)]
    evk = eng.evk_tensor(keys[0], lvl)
    if kind == "keyswitch":
        return eng._ks_fn(lvl), (ct.c1, evk), ALL_PHASES
    if kind == "galois":
        perm = eng.perm_tensor(gs[:1])[0]
        return (eng._galois_fn(lvl), (ct.c0, ct.c1, perm, evk),
                ALL_PHASES | {"ks.automorph"})
    if kind == "hoisted":
        return (eng._hoist_fn(lvl, 2, False),
                (ct.c0, ct.c1, eng.perm_tensor(gs),
                 eng.evk_group_tensor(keys, lvl), None, None, None),
                ALL_PHASES | {"ks.automorph"})
    digits = eng.modup(ct.c1, lvl)
    if kind == "multi_hoisted":
        return (eng._multi_fn(lvl, 2),
                (jnp.stack([ct.c0, ct.c0]), jnp.stack([digits, digits]),
                 eng.perm_tensor(gs), eng.evk_group_tensor(keys, lvl)),
                {"ks.ip", "ks.moddown", "ks.automorph"})
    if kind == "relin":
        return (eng._relin_fn(lvl, False),
                (ct.c0, ct.c1, ct.c1,
                 eng.evk_tensor(ctx.keys.mult_key, lvl)), ALL_PHASES)
    assert kind == "modup_b"
    return (eng._modup_batched_fn(lvl), (jnp.stack([ct.c1, ct.c1]),),
            {"ks.modup"})


@pytest.mark.parametrize("kind", ["keyswitch", "galois", "hoisted",
                                  "multi_hoisted", "relin", "modup_b"])
def test_plan_carries_its_name_and_phase_scopes(tctx, kind):
    plan, args, scopes = _plan_case(tctx, kind)
    name = plan_name(kind, tctx.params.L)
    assert name == f"ks_{kind}_L3"
    text = plan.func.lower(*plan.args, *args).compile().as_text()
    assert f"jit_{name}" in text.splitlines()[0]
    found = {p for p in ("ks.modup", "ks.ip", "ks.moddown", "ks.automorph")
             if p in text}
    assert found == scopes


def test_enabled_span_enters_a_trace_annotation(monkeypatch):
    entered = []

    class Note:
        def __init__(self, name, **kwargs):
            self.name, self.kwargs = name, kwargs

        def __enter__(self):
            entered.append((self.name, self.kwargs))

        def __exit__(self, *exc):
            entered.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Note)
    tracer = Tracer()
    assert tracer.span("off", x=1) is NULL_SPAN
    assert entered == []
    tracer.enable()
    with tracer.span("exec.step.RelinStep", batch_index=3, rids=[1, 2]):
        pass
    tracer.disable()
    assert entered == [("exec.step.RelinStep", {"batch_index": 3}),
                       ("exit", "exec.step.RelinStep")]
    assert tracer.spans("exec.step.*")[0].attrs["rids"] == [1, 2]
    assert tracer.span("off again") is NULL_SPAN


def test_self_seconds_count_nested_intervals_once():
    # keygen 0-10 holds a trace 2-6 that holds a compile 3-4; a compile
    # 8-12 outlives the keygen
    iv = [(0, 10, "keygen"), (2, 6, "trace_lower"), (3, 4, "compile"),
          (8, 12, "compile")]
    assert self_seconds(iv) == {"keygen": 4, "trace_lower": 3,
                                "compile": 5}
    assert sum(self_seconds(iv).values()) == 12
    assert self_seconds([]) == {"keygen": 0.0, "trace_lower": 0.0,
                                "compile": 0.0}


def test_timed_records_an_interval():
    ticks = iter([1.0, 3.5])
    c = SetupCounters(clock=lambda: next(ticks))
    with c.timed("keygen"):
        pass
    assert c.read() == {"trace_lower_s": 0.0, "compile_s": 0.0,
                        "keygen_s": 2.5, "cache_misses": 0,
                        "cache_hits": 0}
    assert c.read(since=2.0, until=3.0)["keygen_s"] == 1.0
    assert c.read(since=4.0)["keygen_s"] == 0.0


@pytest.fixture
def temp_cache(tmp_path):
    """JAX's persistent cache in ``tmp_path`` for one test, every
    compile in it; the process's settings are restored after."""
    from jax._src import compilation_cache as cc

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    yield tmp_path
    for n, v in saved.items():
        jax.config.update(n, v)
    cc.reset_cache()


def test_setup_counters_count_a_miss_then_a_hit(temp_cache):
    def setup_probe(x):
        return jnp.sin(x) * 3.0 + 1.0

    x = jnp.arange(37.0)
    t0 = time.time()
    jax.jit(setup_probe)(x).block_until_ready()
    t1 = time.time()
    first = obs.SETUP.read(since=t0, until=t1)
    assert first["cache_misses"] == 1 and first["cache_hits"] == 0
    assert first["trace_lower_s"] > 0 and first["compile_s"] > 0
    jax.clear_caches()
    jax.jit(setup_probe)(x).block_until_ready()
    again = obs.SETUP.read(since=t1)
    assert again["cache_misses"] == 0 and again["cache_hits"] == 1
    assert again["compile_s"] > 0


#: One process: a small compiled program run twice, batched, with the
#: persistent cache in the directory given; prints the names of the
#: plans it compiled and its set-up counters.
CHILD = textwrap.dedent("""
    import json, sys
    import jax
    jax.config.update("jax_compilation_cache_dir", sys.argv[1])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import numpy as np
    from repro import obs
    from repro.core.ckks import CKKSContext
    from repro.core.params import CKKSParams
    from repro.runtime import ProgramExecutor, TraceContext, compile_program

    names = []
    jax.monitoring.register_event_time_span_listener(
        lambda ev, a, b, fun_name="", **_: names.append(fun_name)
        if ev.endswith("backend_compile_duration") else None)
    p = CKKSParams(logN=8, L=3, alpha=2, k=2, q_bits=29, scale_bits=29)
    ctx = CKKSContext(p, seed=3)
    tc = TraceContext(p)
    x = tc.input("x")
    y = tc.multiply(x, x)
    tc.output(tc.add(tc.rotate(y, 1), tc.rotate(y, 5)), "y")
    prog = compile_program(tc, fusion=True)
    ex = ProgramExecutor(ctx)
    rng = np.random.default_rng(0)
    cts = [ctx.encrypt(rng.normal(size=p.num_slots)) for _ in range(2)]
    for _ in range(2):
        ex.run_batched(prog, {"x": cts})
    print(json.dumps({"plans": sorted({n for n in names
                                       if "ks_" in n}),
                      "setup": obs.SETUP.read()}))
""")


def test_two_processes_name_every_plan_the_same(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src"),
                    os.environ.get("PYTHONPATH", "")]))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)

    def child():
        out = subprocess.run([sys.executable, "-c", CHILD, str(tmp_path)],
                             env=env, capture_output=True, text=True,
                             timeout=600, check=True)
        return json.loads(out.stdout.strip().splitlines()[-1])

    cold, warm = child(), child()
    assert cold["plans"] and cold["plans"] == warm["plans"]
    assert all(re.fullmatch(r"jit\(ks_[a-z_]+_L\d+\)", n)
               for n in cold["plans"]), cold["plans"]
    assert cold["setup"]["cache_misses"] > 0
    assert warm["setup"]["cache_misses"] == 0
    assert warm["setup"]["cache_hits"] >= cold["setup"]["cache_misses"]
