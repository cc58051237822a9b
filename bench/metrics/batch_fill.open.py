"""Front end: share of executed batch slots that held a request (the rest
is padding), from the engine's counters over the window (%)."""


def read(run):
    st = run.window.stats
    slots = st["n_queries"] + st["n_padded"]
    return 100.0 * st["n_queries"] / slots if slots else None
