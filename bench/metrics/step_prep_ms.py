"""Host milliseconds per step that `engine.step` spends before it launches
the step program: its `engine.args` and `engine.lookup` spans in the traced
window (bench.scopes.host_split). Layer: engine."""


def read(ctx):
    split = ctx.host_split
    return None if split is None else split["step_prep_ms"]
