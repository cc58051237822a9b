"""Process start to the first timed request: data, build, compile or cache
load, warm-up (s)."""


def read(run):
    return run.setup_s
