"""Device self time per step of the ops in the ``moe_router`` scope: router
matmul, softmax, top-k, gate normalisation and capacity ranking, ms."""

from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, {"moe_router"})
