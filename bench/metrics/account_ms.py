"""Host time per tick spent accounting fetched verdicts: the program's
`account` spans (call log, wall histogram, and per slot its telemetry,
flag counts and `chunk_retired` event) in the traced stretch over the
ticks in it."""


def read(ctx):
    return ctx.program_ms_per_tick("account")
