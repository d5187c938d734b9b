"""Device milliseconds per plain step (t % rotation_freq != 0) under the
`rotate` scope: the gradient rotated into the eigenbasis and the update
rotated back (bench.scopes.seconds_under), averaged over chips. Layer:
optimizer."""


def read(ctx):
    from bench.scopes import seconds_under

    return seconds_under(ctx, "rotate", "plain")
