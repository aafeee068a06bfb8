"""Device self time per step of the ops in the program's ``mla_latent``
scope: MLA's latent projections (``wdkv``, ``wkr``, ``wdq``), their norms
and the up-projections of the key-value latent (``wuk``, ``wuv``), forward,
remat and backward, ms."""

from bench.scopes_deepseek_v3 import scopes


def read(ctx):
    return scopes.scope_ms(ctx, {"mla_latent"})
