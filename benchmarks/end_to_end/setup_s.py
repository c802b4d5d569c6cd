"""Process start to the first measured dispatch: JAX start, the
small-size comparison, populate, compile or cache load, warm-up and the
warm-up's own checks."""


def read(ctx):
    return ctx["setup_s"]
