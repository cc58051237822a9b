"""Host tier: rate of the exact-row fetches from host memory over the
window, the bytes they returned over the time they took, from the engine's
counters (GB/s). None from a program without the byte counter."""


def read(run):
    st = run.window.stats
    nbytes, us = st.get("host_fetch_bytes"), st.get("host_fetch_us")
    return nbytes / us / 1e3 if nbytes and us else None
