"""``bench/scopes.py`` with the DeepSeek-V3 block's own scopes added.

The program runs MLA's latent projections and norms in ``mla_latent``
(inside ``attn``) and the shared experts in ``moe_shared`` (inside
``moe_experts``).  ``bench/scopes.py`` does not know these names, so it
gives their ops to the enclosing scope; this module is an instance of it
of its own (``harness.load_module``) that knows them, and parses the
cell's step once for the readers that import it.
"""

from bench import harness

scopes = harness.load_module(harness.BENCH_DIR / "scopes.py")
scopes.SCOPES = scopes.SCOPES | {"mla_latent", "moe_shared"}
