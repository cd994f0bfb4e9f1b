"""Bytes fetched from the device per verdict, over the calls whose
dispatch and fetch both lie in the traced stretch: the `d2h_bytes` of
their `retire` spans (the sizes of the output arrays each fetch moves)
over the `samples` of their `dispatch` spans, matched by dispatch
tick."""


def read(ctx):
    sent, got = {}, {}
    for name, _, _, args in ctx.program_spans:
        if name == "dispatch" and "h2d_bytes" in args:
            sent[args["tick"]] = sent.get(args["tick"], 0) + args["samples"]
        elif name == "retire" and "d2h_bytes" in args:
            t = args["dispatch_tick"]
            got[t] = got.get(t, 0) + args["d2h_bytes"]
    both = sent.keys() & got.keys()
    verdicts = sum(sent[t] for t in both)
    return sum(got[t] for t in both) / verdicts if verdicts else None
