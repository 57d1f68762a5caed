"""Serving-layer invariants (``repro.serve``).

Covers the acceptance gates of the multi-tenant serving subsystem:
  * per-tenant FIFO under mixed plan shapes (oldest-head-first groups)
  * zero retraces across packed live traffic (``engine.trace_counts``
    flat after warmup; admission-policy hits account for every batch)
  * bounded-queue backpressure (rejections counted, depth bounded)
  * registry eviction never evicts an in-flight tenant's keys, and
    eviction purges the engine's evk tensor caches
  * metrics arithmetic (nearest-rank p50/p99, throughput)
  * per-tenant correctness: outputs decrypt under the RIGHT tenant key
"""
import numpy as np
import pytest

from repro.core import linear
from repro.core.ckks import CKKSContext
from repro.core.params import CKKSParams
from repro.runtime import TraceContext, compile_program
from repro.serve import (
    Arrival, FHEServer, TenantRegistry, percentile, plan_signature,
    poisson_trace,
)
from repro.serve.metrics import TenantStats
from repro.serve.queue import RequestQueue
from repro.serve.scheduler import ContinuousBatcher

N_DIAG_A, BS_A = 4, 2           # program "a": BSGS matvec
N_DIAG_B = 3                    # program "b": single-block matvec


@pytest.fixture(scope="module")
def sctx():
    params = CKKSParams(logN=8, L=4, alpha=2, k=2, q_bits=29,
                        scale_bits=29)
    return CKKSContext(params, seed=3)


@pytest.fixture(scope="module")
def sprogs(sctx):
    """Two compiled programs with DIFFERENT plan shapes."""
    params = sctx.params
    nh = params.num_slots
    rng = np.random.default_rng(11)
    diags_a = {d: rng.normal(size=nh) for d in range(N_DIAG_A)}
    diags_b = {d: rng.normal(size=nh) for d in range(N_DIAG_B)}

    tc = TraceContext(params)
    h = tc.input("x", level=params.L, scale=params.scale)
    tc.output(linear.matvec_bsgs(tc, h, diags_a, bs=BS_A), "y")
    prog_a = compile_program(tc)

    tc = TraceContext(params)
    h = tc.input("x", level=params.L, scale=params.scale)
    tc.output(linear.matvec_diag(tc, h, diags_b), "y")
    prog_b = compile_program(tc)
    assert plan_signature(prog_a) != plan_signature(prog_b)
    return {"a": (prog_a, diags_a), "b": (prog_b, diags_b)}


def _server(sctx, sprogs, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_wait_s", 0.0)
    server = FHEServer(sctx, **kw)
    for pid, (comp, _) in sprogs.items():
        server.register_program(pid, comp)
    return server


def _inputs_maker(sctx, record=None):
    nh = sctx.params.num_slots
    rng = np.random.default_rng(29)

    def inputs_for(a):
        z = rng.normal(size=nh) + 1j * rng.normal(size=nh)
        if record is not None:
            record.append((a, z))
        return {"x": sctx.encrypt(z)}

    return inputs_for


def _warm(server, sctx, pids, width=None):
    with server.registry.lease("warm"):
        ct0 = sctx.encrypt(np.zeros(sctx.params.num_slots))
    for pid in pids:
        server.warmup("warm", pid, {"x": ct0}, width=width)


def test_fifo_fairness_per_tenant(sctx, sprogs):
    """Mixed plan shapes: within every (tenant, program) batch class
    requests complete in submission order, and batches launch
    oldest-head-first (no tenant's head request is ever bypassed by a
    younger head from another class)."""
    server = _server(sctx, sprogs, max_batch=2)
    trace = poisson_trace(500.0, 20, ["t0", "t1", "t2"], ["a", "b"],
                          seed=5)
    _warm(server, sctx, ["a", "b"])
    log: list = []
    rep = server.run_trace(trace, _inputs_maker(sctx, record=log))
    assert rep.completed == 20
    # rid i <=> i-th admitted arrival (nothing rejected here)
    arrival_of = {rid: a.t for rid, (a, _) in enumerate(log)}
    done: dict[tuple, list[int]] = {}
    for rec in server.records:
        done.setdefault((rec.tenant, rec.program_id), []).extend(rec.rids)
    assert {t for t, _ in done} == {"t0", "t1", "t2"}
    for group, rids in done.items():
        assert rids == sorted(rids), \
            f"class {group} completed out of FIFO order: {rids}"
    heads = [arrival_of[rec.rids[0]] for rec in server.records]
    assert heads == sorted(heads), \
        "scheduler launched a younger batch head before an older one"


def test_zero_retraces_across_packed_batches(sctx, sprogs):
    """After warmup, live traffic never retraces a jit plan: the
    engine's trace_counts stay flat and every batch is an
    admission-policy hit."""
    server = _server(sctx, sprogs, max_batch=2)
    _warm(server, sctx, ["a", "b"])
    before = dict(sctx.engine.trace_counts)
    trace = poisson_trace(500.0, 16, ["t0", "t1", "t2", "t3"],
                          ["a", "b"], seed=9)
    rep = server.run_trace(trace, _inputs_maker(sctx))
    assert rep.completed == 16
    assert dict(sctx.engine.trace_counts) == before, \
        "packed serving retraced a jit plan"
    assert rep.plan_cache["hits"] == rep.batches
    assert rep.plan_cache["misses"] == 2       # the two warmups only


def test_bounded_queue_backpressure(sctx, sprogs):
    """An arrival burst beyond the bound is rejected, counted, and the
    queue depth never exceeds maxsize."""
    server = _server(sctx, sprogs, max_batch=2, queue_size=3)
    _warm(server, sctx, ["a"])
    burst = [Arrival(0.0, f"t{i % 2}", "a") for i in range(8)]
    rep = server.run_trace(burst, _inputs_maker(sctx))
    assert rep.completed == 3
    assert rep.rejected == 5
    assert rep.queue["rejected"] == 5
    assert rep.queue["max_depth"] <= 3
    per_tenant_rej = sum(t["rejected"] for t in rep.tenants.values())
    assert per_tenant_rej == 5


def test_eviction_never_evicts_inflight(sctx):
    """A leased (in-flight) tenant's keys survive registry churn; once
    released, eviction proceeds and purges the engine evk caches."""
    registry = TenantRegistry(sctx, capacity=1, base_seed=7000)
    kc_a = registry.keychain("A")
    with registry.lease("A"):
        # force key material + engine evk tensors for tenant A
        ct = sctx.encrypt(np.ones(sctx.params.num_slots))
        sctx.rotate(ct, 1)
        a_ids = {id(k) for k in kc_a._rot_keys.values()}
        # capacity exceeded while A is in flight: A must NOT be evicted
        registry.keychain("B")
        assert "A" in registry and registry.keychain("A") is kc_a
        assert registry.evictions == 0
    # lease released: the next admission evicts LRU non-inflight (B was
    # bumped by its own creation; A was bumped by the identity check
    # above, so B is LRU)
    registry.keychain("C")
    assert registry.evictions >= 1
    assert len(registry) <= registry.capacity + 1
    # evict A explicitly and check the engine cache purge
    while "A" in registry._chains and registry._evict_one():
        pass
    assert all(k[0] not in a_ids for k in sctx.engine._evk_level)
    assert all(i not in a_ids for i in sctx.engine._evk_full)


@pytest.mark.parametrize("arrival", [0.2, 0.4, 1e3 + 0.3])
def test_batcher_flushes_at_its_flush_time(arrival):
    """A lone request is ready at exactly ``next_flush_time`` — the time
    the idle virtual clock jumps to.  With (arrival + w) - arrival < w
    in floating point (e.g. 0.2 + 0.05), the batcher once stayed
    not-ready there forever and ``run_trace`` never returned."""
    q = RequestQueue(4)
    q.offer("t", "p", {}, arrival)
    b = ContinuousBatcher(max_batch=4, max_wait_s=0.05)
    batch = b.pick(q, b.next_flush_time(q))
    assert batch is not None and len(batch.requests) == 1


def test_metrics_arithmetic():
    """Nearest-rank percentiles + throughput from first principles."""
    lats = [0.1 * k for k in range(1, 11)]          # 0.1 .. 1.0
    assert percentile(lats, 50) == pytest.approx(0.5)
    assert percentile(lats, 99) == pytest.approx(1.0)
    assert percentile(lats, 100) == pytest.approx(1.0)
    assert percentile([0.7], 50) == pytest.approx(0.7)
    # empty sample: None, not a fake 0.0 latency
    assert percentile([], 99) is None
    assert percentile([], 50) is None

    # rank arithmetic at the boundary sizes (nearest-rank definition:
    # sorted[max(1, ceil(p/100 * n)) - 1], clamped into [1, n])
    # n=1: every p returns the sample
    for p in (0.1, 1, 50, 99, 100):
        assert percentile([0.7], p) == pytest.approx(0.7)
    # n=2: p<=50 -> first, p>50 -> second
    two = [1.0, 2.0]
    assert percentile(two, 1) == pytest.approx(1.0)
    assert percentile(two, 50) == pytest.approx(1.0)
    assert percentile(two, 51) == pytest.approx(2.0)
    assert percentile(two, 99) == pytest.approx(2.0)
    assert percentile(two, 100) == pytest.approx(2.0)
    # n=100: rank p exactly (identity on 1..100), p99 is the 99th value
    hundred = [float(k) for k in range(1, 101)]
    assert percentile(hundred, 1) == pytest.approx(1.0)
    assert percentile(hundred, 50) == pytest.approx(50.0)
    assert percentile(hundred, 99) == pytest.approx(99.0)
    assert percentile(hundred, 100) == pytest.approx(100.0)
    # empty-tenant snapshot: percentile fields are None, not 0.0
    empty = TenantStats().summary(span_s=1.0)
    assert empty["p50_latency_s"] is None
    assert empty["p99_latency_s"] is None
    assert empty["completed"] == 0

    st = TenantStats()
    for v in lats:
        st.record(v)
    st.rejected = 2
    s = st.summary(span_s=5.0)
    assert s["completed"] == 10 and s["rejected"] == 2
    assert s["throughput_ops"] == pytest.approx(2.0)
    assert s["p50_latency_s"] == pytest.approx(0.5)
    assert s["p99_latency_s"] == pytest.approx(1.0)
    assert s["mean_latency_s"] == pytest.approx(0.55)


def test_outputs_decrypt_under_tenant_keys(sctx, sprogs):
    """Each served output decrypts correctly under ITS tenant's secret
    key — key material never leaks across the shared engine."""
    server = _server(sctx, sprogs, max_batch=2)
    _warm(server, sctx, ["a"])
    log: list = []
    trace = [Arrival(0.0, "alice", "a"), Arrival(0.0, "bob", "a"),
             Arrival(0.0, "alice", "a"), Arrival(0.0, "bob", "a")]
    rep = server.run_trace(trace, _inputs_maker(sctx, record=log))
    assert rep.completed == 4
    _, diags_a = sprogs["a"]
    for rid, (a, z) in enumerate(log):
        expect = sum(np.asarray(v) * np.roll(z, -d)
                     for d, v in diags_a.items())
        with server.registry.lease(a.tenant):
            got = sctx.decrypt(server.outputs[rid]["y"])
        np.testing.assert_allclose(got, expect, atol=1e-3)
