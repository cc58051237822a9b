"""Device to host: mean time of one batch's answer conversion (ids and
scores to host arrays), from the engine's counters over the window (ms)."""


def read(run):
    st = run.window.stats
    n = st.get("n_d2h", 0)
    return st["d2h_us"] / n / 1e3 if n else None
