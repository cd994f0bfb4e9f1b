"""Host time per tick that no phase of the tick names: for each of the
program's `tick` spans in the traced stretch, its duration minus the
union of the other program spans inside it (a nested span, such as
`acquire` inside `admission`, counts once), averaged over the ticks."""


def read(ctx):
    spans = [(a, b) for name, a, b, _ in ctx.program_spans
             if name != "tick"]
    ticks = [(a, b) for name, a, b, _ in ctx.program_spans
             if name == "tick"]
    if not ticks:
        return None
    self_s = 0.0
    for t0, t1 in ticks:
        covered, end = 0.0, t0
        for a, b in sorted((max(a, t0), min(b, t1)) for a, b in spans
                           if a < t1 and b > t0):
            if b > end:
                covered += b - max(a, end)
                end = b
        self_s += (t1 - t0) - covered
    return 1e3 * self_s / len(ticks)
