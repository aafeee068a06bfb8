"""Device self time per step of the ops in the ``moe_dispatch`` and
``moe_combine`` scopes: the scatter of tokens into the expert capacity
buffer and the gather of the expert outputs back, with their backward, ms."""

from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, {"moe_dispatch", "moe_combine"})
