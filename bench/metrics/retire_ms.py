"""Host time of the scheduler's retirement per tick: the program's
`retire` spans in the traced stretch over the ticks in it.  The span
covers the fetch of a call's flags and member bitmask to the host, the
wait for the device included; the per-slot accounting after it lies
outside the span."""


def read(ctx):
    return ctx.program_ms_per_tick("retire")
