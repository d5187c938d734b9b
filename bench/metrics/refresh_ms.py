"""Device milliseconds that a basis-refresh step takes over a plain step:
the mean step-program time of steps with t % rotation_freq == 0, less that
of the others, averaged over chips. Layer: optimizer."""


def read(ctx):
    from bench.stepprog import step_durations

    per_chip = step_durations(ctx)
    freq = int(ctx.cell.options["rotation_freq"])
    out = []
    for durs in per_chip or []:
        ref = [d for t, d in zip(ctx.steps, durs) if t % freq == 0]
        plain = [d for t, d in zip(ctx.steps, durs) if t % freq != 0]
        if not ref or not plain:
            return None
        out.append(1e3 * (sum(ref) / len(ref) - sum(plain) / len(plain)))
    return sum(out) / len(out) if out else None
