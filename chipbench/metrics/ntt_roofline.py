"""Per cent of the HBM roofline that the ``ntt`` kernel reaches: its
least bytes (``costs/ntt.py``) over the chip's HBM bandwidth, over its
device time in the window."""
from chipbench import kernels


def read(run: dict):
    return kernels.roofline(run["trace"], "ntt", run["device_kind"])
