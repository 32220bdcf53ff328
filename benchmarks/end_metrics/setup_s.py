"""Process start to the first measured request: corpus from the seed,
install, upload, compile or cache load, warm-up."""


def read(ctx):
    return ctx["setup_s"]
