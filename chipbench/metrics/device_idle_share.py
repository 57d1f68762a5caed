"""Per cent of the traced window in which no op ran on the device."""
from chipbench import trace


def read(run: dict):
    share = trace.idle_share(run["trace"])
    return None if share is None else 100.0 * share
