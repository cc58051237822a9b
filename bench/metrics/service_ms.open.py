"""Engine: mean time from a request's batch dispatch to its answer being
recorded (transfers, device work, host fetch, waits for the batches ahead
in the pipeline), from the engine's counters over the window (ms)."""


def read(run):
    st = run.window.stats
    n = st["n_queries"] - st.get("n_cache_hits", 0)
    if "service_us" not in st or not n:
        return None
    return st["service_us"] / n / 1e3
