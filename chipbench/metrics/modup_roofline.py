"""Per cent of the HBM roofline that the ``modup`` kernel reaches: its
least bytes (``costs/modup.py``) over the chip's HBM bandwidth, over its
device time in the window."""
from chipbench import kernels


def read(run: dict):
    return kernels.roofline(run["trace"], "modup", run["device_kind"])
