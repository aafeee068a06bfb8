"""Device self time per step of the ops in the program's ``attn`` scope:
q, k, v and o projections, RoPE and flash attention, forward, remat and
backward, ms."""

from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, {"attn"})
