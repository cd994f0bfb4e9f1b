"""Host time per tick of the ensemble's per-member accounting: the
program's `members` spans (inside `account`: every member slot's
per-detector flag counts, score sums and bitmask rows) in the traced
stretch over the ticks in it.  A program that records no `members`
span reads nothing."""


def read(ctx):
    return ctx.program_ms_per_tick("members")
