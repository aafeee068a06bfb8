"""Device self time per step of the ops in the ``adam`` scope: global norm,
clipping, moment and parameter updates, ms."""

from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, {"adam"})
