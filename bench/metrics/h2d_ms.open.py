"""Host to device: mean host time per batch of its transfers to the device
(the query batch, and on a host-tier bank the fetched rescore rows), from
the engine's counters over the window (ms)."""


def read(run):
    st = run.window.stats
    n = st["n_batches"]
    return st["h2d_us"] / n / 1e3 if "h2d_us" in st and n else None
