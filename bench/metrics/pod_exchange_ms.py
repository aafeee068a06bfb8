"""Device self time per step of the ops in the program's ``pod_exchange``
scope: the geococo filter, its mask and the exchange's collectives across
pods, ms."""

from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, {"pod_exchange"})
