"""JAX backend compiles (or persistent-cache loads) in the traced
stretch: the count of the program's `compile` spans in it, each one a
stall of the served window.  A program that records no `tick` spans
records no `compile` spans either, and reads nothing."""


def read(ctx):
    if not any(s[0] == "tick" for s in ctx.program_spans):
        return None
    return sum(1 for s in ctx.program_spans if s[0] == "compile")
