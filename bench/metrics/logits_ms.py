"""Device self time per step of the ops in the ``embed`` and ``logits``
scopes: the vocabulary-wide work (token lookup and its scatter into the
table, final norm, unembedding, log-softmax and loss), ms."""

from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, {"embed", "logits"})
