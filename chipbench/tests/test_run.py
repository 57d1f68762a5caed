"""The harness end to end on the CPU, at a small ring, with the look for a
chip and the check of compiled kernels skipped and the exact jnp engine
in place of the Pallas one: a sound run is correct, and a run whose
timed path is broken underneath is not."""
import json

import numpy as np
import pytest

from chipbench import run

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}

#: A HELR ciphertext of 128 samples, as at full size, of 16 features
#: (logN=12); weights in [-2.25, 2.25] keep w.z's standard deviation at
#: 3, as at full size.
SMALL_HELR = {"features": 16, "w_range": 2.25}


def small_cell(workload: str, logN: int = 8, **params) -> run.Cell:
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    cell = run.Cell(bench, workload)
    cell.cfg["params"].update(logN=logN, **params)
    if cell.entry["config"] == "helr-paper":
        cell.cfg.update(SMALL_HELR)
    return cell


@pytest.fixture(autouse=True)
def on_cpu(monkeypatch):
    """The jnp engine in place of the Pallas one, whose kernels run in
    interpret mode on the CPU, and no check for compiled kernels."""
    from repro.core import ckks

    real = ckks.CKKSContext

    def jnp_context(params, seed, backend):
        return real(params, seed=seed, backend="jnp")

    monkeypatch.setattr(ckks, "CKKSContext", jnp_context)
    monkeypatch.setattr(run, "check_compiled", lambda ctx: None)


@pytest.fixture
def helr():
    return small_cell("helr-paper.serial", logN=12)


def test_sound_run_is_correct(helr):
    line = run.measure(helr, 2**31 + 7, 0.5, False, CPU)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {m["name"] for m in helr.end_to_end}
    assert list(line)[-1] == "checks"


def test_altered_answer_is_not_correct(helr, monkeypatch):
    sound = run.Setup.run

    def altered(self, i):
        outs = sound(self, i)
        ct = outs["w2"][0]
        ct.c0 = ct.c0.at[0, 0].set((ct.c0[0, 0] + 1) % 2**20)
        return outs

    monkeypatch.setattr(run.Setup, "run", altered)
    line = run.measure(helr, 2**31 + 8, 0.5, False, CPU)
    assert not line["correct"]
    assert line["checks"]["max_err"]["value"] > 1e3


def _drop_t(prog):
    """The inner products dropped: the mask keeps nothing, so the
    sigmoid reads 1/2 everywhere."""
    sound = prog._mul_vec
    return "_mul_vec", lambda ctx, ct, vec, s: sound(ctx, ct, 0 * vec, s)


def _wrong_step(prog):
    """The first rotate-and-sum's last rotation by one slot too few."""
    sound = prog.rotations

    def rotations(cfg, nh):
        rots = sound(cfg, nh)
        return dict(rots, sum=rots["sum"][:-1] + [rots["sum"][-1] - 1])
    return "rotations", rotations


@pytest.mark.parametrize("fault", [_drop_t, _wrong_step])
def test_inner_product_fault_is_not_correct(helr, monkeypatch, fault):
    """A fault upstream of the sigmoid, in the compiled program only
    (the reference is untouched), fails the limit."""
    monkeypatch.setattr(helr.program, *fault(helr.program))
    line = run.measure(helr, 2**31 + 11, 0.5, False, CPU)
    assert not line["correct"], line["checks"]


def test_control_is_not_correct(helr):
    """The control: the same program at the configuration's lower
    scale (``control`` in its file) fails the limit."""
    cell = small_cell("helr-paper.serial", logN=12,
                      **helr.cfg["control"]["params"])
    line = run.measure(cell, 2**31 + 9, 0.5, False, CPU)
    err = line["checks"]["max_err"]
    assert not line["correct"] and np.isfinite(err["value"]), err


def test_no_chip_exits_nonzero(capsys):
    assert run.main(["--workload", "helr-paper.serial", "--seed", "1",
                     "--seconds", "1"]) == 1
    assert capsys.readouterr().out == ""


def test_half_batch_left_out_is_not_correct(monkeypatch):
    """Batch 4: the outputs of the second half of each batch replaced
    by those of the first half."""
    cell = small_cell("logreg-paper.b4")
    sound = run.Setup.run

    def halved(self, i):
        outs = sound(self, i)
        half = len(outs["y"]) // 2
        outs["y"][half:] = outs["y"][:half]
        return outs

    monkeypatch.setattr(run.Setup, "run", halved)
    line = run.measure(cell, 2**31 + 10, 0.5, False, CPU)
    assert not line["correct"], line["checks"]
