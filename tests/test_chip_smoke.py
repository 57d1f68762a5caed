"""``chip_smoke.py`` kept runnable: its phases at small rings on the CPU.

The script runs only on a TPU; these tests import it and call its phase
functions directly, in interpret mode, with parameters passed in — the
program gains no option for it.  ``SMALL_TEST_PARAMS`` cannot hold the
logreg: its 5-level chain is shorter than the 9 levels the degree-15
sigmoid consumes, and with k = alpha and a 2^28 scale its keyswitch
noise swamps the result (max error ~14 at logN=8).  The logreg phase
therefore runs at the ``bench_workloads`` shape (logN=8, L=14, k=3,
29-bit words and scale); the bootstrap phase has a file of its own
(``test_chip_smoke_boot.py``) so the two slow tests run on different
test workers.  On the CPU the "host reference" shares the device with
the engine under test, so these tests pin the phases' control flow and
checks, not the chip.
"""
import pytest

from repro.core.params import CKKSParams

LOGREG_PARAMS = CKKSParams(logN=8, L=14, alpha=2, k=3, q_bits=29,
                           scale_bits=29)


def test_refuses_to_run_without_tpu(smoke, monkeypatch, tmp_path, capsys):
    """No CPU fallback: non-zero exit, no result line."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    with pytest.raises(smoke.NoAccelerator):
        smoke.require_tpu()
    assert smoke.main() == 1
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


def test_logreg_serve_and_fused_phases(smoke, traced):
    ctx, host, progs, model, cts, xs, chip = smoke.phase_logreg(
        LOGREG_PARAMS, batch=2, seed=0, interpret=True, cpu_err=None)
    chip["fused"] = smoke.phase_fused(ctx, progs["fused"], model, cts, xs,
                                      interpret=True)
    smoke.phase_serve(ctx, progs["exact"], model, batch=2, seed=0,
                      n_requests=3, interpret=True)
    smoke.check_host(ctx, host, model, xs[0], chip)


def test_host_context_reproduces_engine(smoke):
    """The host reference context, copied before any key exists,
    generates the engine's keys itself and gives its hoisted block bit
    for bit."""
    import numpy as np

    from repro.core.ckks import CKKSContext

    ctx = CKKSContext(LOGREG_PARAMS, seed=3)
    model = smoke.BandedLogreg(LOGREG_PARAMS.num_slots, seed=3)
    ct = ctx.encrypt(model.sample(np.random.default_rng(3)))
    hctx = smoke.host_context(ctx)
    exp = model.first_block_sum(ctx, ct)
    with smoke.on_host():
        got = model.first_block_sum(hctx, smoke.host_ct(ct))
    assert smoke._same([got], [exp])
    assert sorted(hctx.keys._rot_keys) == sorted(ctx.keys._rot_keys) \
        == [1, 2, 3]
