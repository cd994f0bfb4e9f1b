"""Host time of one scheduler tick: the mean of the harness's spans
around `BatchingScheduler.step()` in the traced stretch."""


def read(ctx):
    d = [b - a for name, a, b in ctx.spans if name == "step"]
    return 1e3 * sum(d) / len(d) if d else None
