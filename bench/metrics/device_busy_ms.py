"""Device time per completed step: the union of the intervals in which an
operation ran, over the traced window, divided by its steps, ms."""


def read(ctx):
    s = ctx.summary
    if not s.steps:
        return None
    return s.busy_s / s.steps * 1e3
