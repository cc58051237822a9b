"""Front end: mean time a request the device answered waited in the
scheduler's queue, from its submit to its batch's dispatch, from the
engine's counters over the window (ms)."""


def read(run):
    st = run.window.stats
    n = st["n_queries"] - st.get("n_cache_hits", 0)
    if "queue_wait_us" not in st or not n:
        return None
    return st["queue_wait_us"] / n / 1e3
