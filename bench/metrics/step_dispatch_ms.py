"""Host milliseconds per `engine.step` call in the traced window: the
harness's `dispatch` span around the loop's call. Layer: loop."""


def read(ctx):
    if not ctx.dispatch_s:
        return None
    return 1e3 * sum(ctx.dispatch_s) / len(ctx.dispatch_s)
