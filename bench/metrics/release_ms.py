"""Host time per tick spent releasing slots: the program's `release`
spans (each `SlotPool.release`, its detach and any pool shrink; they
nest in `complete`) in the traced stretch over the ticks in it.  A
stretch with no release in it reads 0.  A program that records no
`tick` spans, or whose `SlotPool.SPANS` does not name `release` (it
records none, whatever the stretch holds), reads nothing."""


def read(ctx):
    from repro.engine.pool import SlotPool
    if ("release" not in getattr(SlotPool, "SPANS", ())
            or not any(s[0] == "tick" for s in ctx.program_spans)):
        return None
    return ctx.program_ms_per_tick("release") or 0.0
