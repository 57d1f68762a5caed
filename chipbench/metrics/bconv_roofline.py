"""Per cent of the HBM roofline that the ``bconv`` kernel reaches: its
least bytes (``costs/bconv.py``) over the chip's HBM bandwidth, over its
device time in the window."""
from chipbench import kernels


def read(run: dict):
    return kernels.roofline(run["trace"], "bconv", run["device_kind"])
