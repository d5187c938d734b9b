"""Device milliseconds per step of the backward pass: the step program's ops
under a `transpose(...)` of the program's scopes, each instant to the
innermost op (bench.scopes), averaged over chips. Layer: model."""


def read(ctx):
    split = ctx.device_split
    return None if split is None else split["bwd_ms"]
