"""Host time per tick spent admitting requests: the program's
`admission` spans (the deficit round over the class queues, each slot
acquisition with its active-mask fetch and any pool resize) in the
traced stretch over the ticks in it.  A stretch with no admission in
it reads 0; a program that records no `tick` spans has no such span,
and reads nothing."""


def read(ctx):
    if not any(s[0] == "tick" for s in ctx.program_spans):
        return None
    return ctx.program_ms_per_tick("admission") or 0.0
