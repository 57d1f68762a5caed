"""ModUps dispatched per request over the window (``OpCounters``)."""


def read(run: dict):
    return run["counters"]["modup"] / run["requests"]
