"""Device self time per step of the ops in no named scope of the program
(norms, residual adds, loop control, copies), ms: what a layer metric
loses when work moves out of its scope shows up here."""

from bench import scopes


def read(ctx):
    return scopes.unscoped_ms(ctx)
