"""Device milliseconds per plain step (t % rotation_freq != 0) of the
optimizer: the step program's ops under `clip` or `opt` and outside the
model's scopes, the rotation among them (bench.scopes), averaged over
chips. Layer: optimizer."""


def read(ctx):
    split = ctx.device_split
    return None if split is None else split["opt_ms"]
