"""Device milliseconds per step of the forward pass: the step program's ops
under the `embed`, `blocks`, `head` or `exchange` scopes and outside any
`transpose(...)`, each instant to the innermost op (bench.scopes), averaged
over chips. Layer: model."""


def read(ctx):
    split = ctx.device_split
    return None if split is None else split["fwd_ms"]
