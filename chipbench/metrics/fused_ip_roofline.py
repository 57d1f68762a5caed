"""Per cent of the HBM roofline that the ``fused_ip`` kernel reaches: its
least bytes (``costs/fused_ip.py``) over the chip's HBM bandwidth, over its
device time in the window."""
from chipbench import kernels


def read(run: dict):
    return kernels.roofline(run["trace"], "fused_ip", run["device_kind"])
