"""``chip_smoke.py``'s bootstrap phase on the CPU, in interpret mode.

At the script's own ring (the ``bench_bootstrap --smoke`` chain,
logN=8).  A separate file from ``test_chip_smoke.py`` so that the two
slow tests run on different test workers.
"""
from repro.core.params import CKKSParams


def test_bootstrap_phase(smoke, traced):
    smoke.phase_bootstrap(CKKSParams(**smoke.BOOT_PARAMS), seed=0,
                          boot=smoke.BOOT, interpret=True)
