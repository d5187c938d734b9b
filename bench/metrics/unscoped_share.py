"""Share of the step program's busy device time, in %, in ops under none of
the program's scopes: the step's own loop ops, parameter copies, XLA's
prefetches (bench.scopes), averaged over chips. Layer: engine."""


def read(ctx):
    split = ctx.device_split
    return None if split is None else split["unscoped_share"]
