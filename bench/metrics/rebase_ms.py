"""Device milliseconds per refresh step (t % rotation_freq == 0) under the
`rebase` scope: the first moment re-expressed in the new eigenbasis, inside
the refresh (bench.scopes.seconds_under), averaged over chips. Layer:
optimizer."""


def read(ctx):
    from bench.scopes import seconds_under

    return seconds_under(ctx, "rebase", "refresh")
