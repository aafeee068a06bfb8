"""Device self time per step of the ops in the ``moe_experts`` scope: the
expert einsums and SwiGLU (and shared experts), ms."""

from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, {"moe_experts"})
