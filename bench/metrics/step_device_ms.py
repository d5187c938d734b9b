"""Device milliseconds of the step program per step, from the trace's
program executions, averaged over chips. Layer: engine."""


def read(ctx):
    from bench.stepprog import step_durations

    per_chip = step_durations(ctx)
    if not per_chip:
        return None
    means = [1e3 * sum(d) / len(d) for d in per_chip]
    return sum(means) / len(means)
