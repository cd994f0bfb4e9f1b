"""Host time of the pool and engine call per tick: the program's
`dispatch` spans (`SlotPool.process` down to the kernel's enqueue) in
the traced stretch over the ticks in it."""


def read(ctx):
    return ctx.program_ms_per_tick("dispatch")
