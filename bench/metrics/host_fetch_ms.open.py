"""Host tier: mean time of one batch's exact-row fetch from host memory,
from the engine's counters over the window (ms)."""


def read(run):
    st = run.window.stats
    n = st["n_host_fetches"]
    return st["host_fetch_us"] / n / 1e3 if n else None
