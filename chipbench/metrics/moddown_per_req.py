"""ModDowns dispatched per request over the window (``OpCounters``)."""


def read(run: dict):
    return run["counters"]["moddown"] / run["requests"]
