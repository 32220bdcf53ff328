"""Real rows per batch the scheduler launched in the window: ``delivered``
÷ ``batches_launched`` from ``ContinuousBatchScheduler.stats()``."""


def read(ctx):
    b, a = ctx["before"]["scheduler"], ctx["after"]["scheduler"]
    launched = a["batches_launched"] - b["batches_launched"]
    if launched <= 0:
        return None
    return (a["delivered"] - b["delivered"]) / launched
