#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python chipbench/run.py --workload helr-paper.serial --seed 7 \
        --seconds 30 --trace 0

One process on one chip, from the root of a checkout.  The cell's entry
in ``BENCHMARK.json`` names its configuration
(``chipbench/configs/<config>.json`` for the sizes, ``<config>.py`` for
the program and its plain reference) and its traffic
(``chipbench/traffic/<traffic>.json``).  Set-up builds the context and
keys, compiles the program, encrypts a pool of requests from ``--seed``
and runs one request twice, the first time with the program's spans on;
the window then runs whole requests, closed loop,
until ``--seconds`` have passed.  After the window the outputs of a
sample of requests are checked (``chipbench/checks.py``).

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` a short window is traced by the JAX profiler and the
metrics are the cell's per-layer metrics, each read by
``chipbench/metrics/<name>.py``.  The last line of standard output is
one JSON object; the compared numbers and their limits are the last
lines of standard error.  Without a TPU, or with fewer chips than the
cell asks for, the run exits with code 1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import checks  # noqa: E402
from chipbench import trace as tr  # noqa: E402

#: Traces and the reduced trace of the last traced run, in the checkout.
TRACE_DIR = ROOT / ".chipbench" / "trace"
#: JAX's persistent compilation cache, at a fixed path in the checkout.
JAX_CACHE = ROOT / ".jax_cache"
LAST_TRACE = ROOT / ".chipbench" / "last_trace.json"
GIB = float(1 << 30)


class NoChip(RuntimeError):
    pass


def log(tag: str, **fields) -> None:
    items = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{tag}] {items}", file=sys.stderr, flush=True)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + re.sub(r"\W", "_", path.stem), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``BENCHMARK.json``'s ``workloads``, with the files
    its configuration and traffic name."""

    def __init__(self, bench: dict, name: str):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(has {sorted(cells)})")
        self.entry = cells[name]
        self.chips = self.entry["chips"]
        cfg = self.entry["config"]
        self.cfg = json.loads((HERE / "configs" / f"{cfg}.json").read_text())
        self.program = load_module(HERE / "configs" / f"{cfg}.py")
        self.traffic = json.loads(
            (HERE / "traffic" / f"{self.entry['traffic']}.json").read_text())

        def listed(m):
            return name in m.get("workloads", [name])
        self.end_to_end = [m for m in bench["end_to_end"] if listed(m)]
        self.per_layer = [m for m in bench["per_layer"] if listed(m)]


class CompileLog:
    """XLA backend compiles, persistent-cache loads included, counted
    through ``jax.monitoring``: a warm run counts as many as a cold one,
    in far fewer seconds.  ``take`` returns and resets the tally."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n, self.s = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.n += 1
            self.s += duration

    def take(self) -> tuple[int, float]:
        out = (self.n, self.s)
        self.n, self.s = 0, 0.0
        return out


def device_info(chips: int) -> dict:
    """The first ``chips`` devices as JAX reports them; raises
    ``NoChip`` unless they are TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU attached (JAX platform {devs[0].platform!r}); "
                     f"this benchmark has no CPU fallback")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def peak_bytes() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def dispatch_events() -> list[dict]:
    """Every ``engine.kernel_dispatch`` event the obs tracer holds."""
    from repro import obs

    evs = [a for n, _, _, a in obs.TRACER.instants
           if n == "engine.kernel_dispatch"]
    for sp in obs.TRACER.spans():
        evs += [a for n, _, a in sp.events if n == "engine.kernel_dispatch"]
    return evs


def check_compiled(ctx) -> None:
    """The engine is the Pallas one with Mosaic-compiled kernels, and
    every recorded kernel dispatch agrees."""
    if ctx.engine.backend != "pallas" or ctx.engine.interpret:
        raise RuntimeError(f"engine backend={ctx.engine.backend} "
                           f"interpret={ctx.engine.interpret}")
    evs = [e for e in dispatch_events() if e["backend"] == "pallas"]
    if not evs:
        raise RuntimeError("no pallas kernel dispatch was recorded")
    bad = [e for e in evs if e["interpret"]]
    if bad:
        raise RuntimeError(f"{len(bad)} kernel dispatches ran in "
                           f"interpret mode: {bad[:3]}")


class Setup:
    """Context, keys, compiled program and the encrypted request pool
    of one cell, built from the seed."""

    def __init__(self, cell: Cell, seed: int):
        import numpy as np

        from repro.core.ckks import CKKSContext
        from repro.core.params import CKKSParams
        from repro.runtime import (
            ProgramExecutor, TraceContext, compile_program,
        )

        cfg, prog = cell.cfg, cell.program
        self.cell = cell
        self.phases = {}
        t0 = time.perf_counter()
        self.params = CKKSParams(**cfg["params"])
        self.ctx = CKKSContext(self.params, seed=seed, backend="pallas")
        self.phases["context_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        tc = TraceContext(self.params)
        level = cfg["input_level"]
        outs = prog.program(
            tc, {t: tc.input(t, level=level) for t in prog.INPUTS}, cfg)
        for tag, h in outs.items():
            tc.output(h, tag)
        self.compiled = compile_program(tc, fusion=cfg["fusion"])
        self.executor = ProgramExecutor(self.ctx)
        self.phases["lower_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        rng = np.random.default_rng(seed)
        traffic = cell.traffic
        nh = self.params.num_slots
        self.values = [prog.sample(rng, cfg, nh)
                       for _ in range(traffic["pool"])]
        self.cts = [{t: self.ctx.encrypt(v[t], level=level)
                     for t in prog.INPUTS} for v in self.values]
        self.order = [int(i) for i in rng.permutation(traffic["pool"])]
        self.phases["pool_s"] = time.perf_counter() - t0
        self.check_rng = rng
        self.batch = traffic["batch"]

    def pick(self, i: int) -> list[int]:
        """Pool indices of the requests in batch ``i``."""
        n = len(self.order)
        return [self.order[(i * self.batch + j) % n]
                for j in range(self.batch)]

    def run(self, i: int):
        """Batch ``i`` through the compiled program; returns its outputs
        (tag -> one ciphertext per request) once the device is done."""
        import jax

        idx = self.pick(i)
        with jax.profiler.TraceAnnotation("dispatch"):
            res = self.executor.run_batched(
                self.compiled,
                {t: [self.cts[k][t] for k in idx]
                 for t in self.cell.program.INPUTS})
        outs = {t: list(cts) for t, cts in res.outputs.items()}
        with jax.profiler.TraceAnnotation("block"):
            jax.block_until_ready([(c.c0, c.c1) for cts in outs.values()
                                   for c in cts])
        return outs


def window(setup: Setup, seconds: float, max_batches: int | None,
           keep: int):
    """Closed loop: whole batches, one after another, until ``seconds``
    have passed (or ``max_batches`` ran).  Returns per-batch latencies,
    the elapsed seconds (last batch included) and the last ``keep``
    batches' (index, outputs)."""
    import jax

    lat = []
    kept = collections.deque(maxlen=keep)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("window"):
        i = 0
        while True:
            ts = time.perf_counter()
            with jax.profiler.TraceAnnotation("request"):
                outs = setup.run(i)
            te = time.perf_counter()
            lat.append(te - ts)
            kept.append((i, outs))
            i += 1
            if te - t0 >= seconds or (max_batches and i >= max_batches):
                break
    return lat, te - t0, list(kept)


def percentile(values, p: float) -> float:
    """The ``p``-th percentile, as ``statistics.quantiles`` (inclusive)
    gives it."""
    if len(values) == 1:
        return values[0]
    return statistics.quantiles(values, n=100, method="inclusive")[
        round(p) - 1]


def end_to_end(cell: Cell, lat, elapsed, setup_s, peak) -> dict:
    """The cell's end-to-end metrics.  A batch's latency counts once for
    each request in it."""
    per_req = [x for x in lat for _ in range(cell.traffic["batch"])]
    out = {}
    for m in cell.end_to_end:
        name = m["name"]
        if name == "requests_per_s":
            v = len(per_req) / elapsed
        elif name == "setup_s":
            v = setup_s
        elif name == "peak_hbm_gib":
            v = peak / GIB
        elif (hit := re.fullmatch(r"latency_p(\d+)_s", name)):
            v = percentile(per_req, float(hit.group(1)))
        else:
            raise KeyError(f"no end-to-end metric {name!r}")
        out[name] = {"value": v, "unit": m["unit"]}
    return out


def per_layer(cell: Cell, run: dict) -> dict:
    """The cell's per-layer metrics, each from its reader
    ``chipbench/metrics/<name>.py``; a reader that finds nothing to
    read returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        v = load_module(HERE / "metrics" / f"{m['name']}.py").read(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def measure(cell: Cell, seed: int, seconds: float, traced: bool,
            device: dict) -> dict:
    """Set-up, window and checks of one run; returns the result line."""
    import jax

    from repro import obs

    compiles = CompileLog()
    setup = Setup(cell, seed)
    t = time.perf_counter()
    obs.enable()                       # dispatch events of the warm-up
    setup.run(0)
    obs.disable()
    check_compiled(setup.ctx)
    obs.reset()
    setup.run(0)                       # the window's path, without spans
    setup.phases["warmup_s"] = time.perf_counter() - t
    setup_s = time.perf_counter() - T_START
    n, s = compiles.take()
    log("setup", setup_s=setup_s, xla_compiles=n, xla_compile_s=s,
        compile_cache=JAX_CACHE,
        steps=len(setup.compiled.steps), **setup.phases)

    traffic = cell.traffic
    before = setup.ctx.counters.snapshot()
    # what set-up left is not collected inside the window
    gc.collect()
    gc.freeze()
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    lat, elapsed, kept = window(
        setup, seconds, traffic["trace_batches"] if traced else None,
        traffic["keep"])
    if traced:
        jax.profiler.stop_trace()
    gc.unfreeze()
    counters = setup.ctx.counters.delta(before).as_dict()
    peak = peak_bytes()
    n, s = compiles.take()
    slow = sorted(range(len(lat)), key=lat.__getitem__)[-3:]
    log("window", batches=len(lat), elapsed_s=elapsed, xla_compiles=n,
        xla_compile_s=s, peak_bytes=peak,
        median_s=statistics.median(lat),
        slowest=[(i, round(lat[i], 4)) for i in reversed(slow)])

    result = checks.run_checks(setup, kept, traffic)
    dev = dict(device, memory_peak_bytes=peak)
    line = {"correct": result["correct"],
            "attempted": len(lat) * traffic["batch"], "failed": 0}
    if traced:
        trace = tr.load_xplane(str(TRACE_DIR))
        LAST_TRACE.write_text(json.dumps(trace))
        run = {"requests": len(lat) * traffic["batch"], "trace": trace,
               "counters": counters, "device_kind": device["kind"]}
        line["metrics"] = per_layer(cell, run)
        dev.update(busy_s=tr.busy_ns(trace) / 1e9,
                   window_s=tr.window_ns(trace) / 1e9)
        line["device"] = dev
        line["breakdown"] = tr.breakdown(trace)
    else:
        line["metrics"] = end_to_end(cell, lat, elapsed, setup_s, peak)
        line["device"] = dev
    line["checks"] = result["checks"]
    return line


def use_compile_cache() -> None:
    """JAX's persistent compilation cache in the checkout, whatever the
    environment names, unbounded, and every compile in it, also the
    sub-second eager ones, so that only a checkout's first run compiles.  The
    program's ``repro.compile_cache`` takes the same directory."""
    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(JAX_CACHE)
    jax.config.update("jax_compilation_cache_dir", str(JAX_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # no eviction: a size limit from the environment evicts this run's
    # own entries while it writes them, and the next run compiles again
    jax.config.update("jax_compilation_cache_max_size", -1)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("chipbench: src/repro not found next to chipbench/; run from "
              "a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    cell = Cell(json.loads((ROOT / "BENCHMARK.json").read_text()),
                args.workload)
    try:
        device = device_info(cell.chips)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    use_compile_cache()
    (ROOT / ".chipbench").mkdir(exist_ok=True)
    line = measure(cell, args.seed, args.seconds, bool(args.trace), device)
    for name, c in line["checks"].items():
        print(f"[check] {name}={c['value']} limit={c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
