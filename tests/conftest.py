"""Shared fixtures.  Small parameter sets keep the full scheme fast on CPU.

NOTE: device count must stay 1 here — the multi-pod dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 in its own process
(see src/repro/launch/dryrun.py), never globally.
"""
import numpy as np
import pytest


@pytest.fixture(scope="session")
def small_params():
    from repro.core.params import CKKSParams

    # k > alpha suppresses keyswitch noise (X_j/P ~ 2^-29 per extra prime).
    return CKKSParams(logN=9, L=5, alpha=2, k=3, q_bits=29, scale_bits=29)


@pytest.fixture(scope="session")
def ctx(small_params):
    from repro.core.ckks import CKKSContext

    return CKKSContext(small_params, seed=7)


@pytest.fixture()
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="session")
def smoke():
    """``chip_smoke.py`` imported as a module (its phases are unit-tested
    on the CPU; the script itself only runs on a TPU)."""
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parent.parent / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def traced():
    """obs tracing on for one test (the smoke checks dispatch events)."""
    from repro import obs

    obs.reset()
    obs.enable()
    yield
    obs.disable()
    obs.reset()
