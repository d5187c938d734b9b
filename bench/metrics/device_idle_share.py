"""Share of the traced window in which no operation runs on a chip, in %,
averaged over the cell's chips. Layer: device."""


def read(ctx):
    from bench import trace as tr

    if not ctx.trace.chips:
        return None
    w = ctx.trace.window[1] - ctx.trace.window[0]
    idle = [1.0 - tr.total(tr.busy(c)) / w for c in ctx.trace.chips]
    return 100.0 * sum(idle) / len(idle)
