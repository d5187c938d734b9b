"""The step program's share of the chips' bf16 peak while it runs, in %:
model FLOPs of a step (bench.counts) over chips x peak x device time per
step. Layer: engine. A kernel's roofline can rise only as far as this
bounds it."""


def read(ctx):
    from bench.stepprog import step_durations

    per_chip = step_durations(ctx)
    if not per_chip or not ctx.peaks:
        return None
    sec = sum(sum(d) / len(d) for d in per_chip) / len(per_chip)
    return 100.0 * ctx.flops_per_step / (len(per_chip) * ctx.peaks["bf16_flops_per_s"] * sec)
