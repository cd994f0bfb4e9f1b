"""Host time per tick spent building the call's inputs: the program's
`assemble` spans (the chunk length chosen, the (t, C) chunk and the
valid-length vector filled slot by slot from each stream's backlog) in
the traced stretch over the ticks in it."""


def read(ctx):
    return ctx.program_ms_per_tick("assemble")
