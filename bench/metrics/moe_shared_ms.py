"""Device self time per step of the ops in the program's ``moe_shared``
scope: the shared experts every token passes through, forward, remat and
backward, ms."""

from bench.scopes_deepseek_v3 import scopes


def read(ctx):
    return scopes.scope_ms(ctx, {"moe_shared"})
