"""Host time the trainer waits for each batch: mean length of the
``bench.data`` spans around ``SyntheticLM.batch`` in the traced window, ms."""


def read(ctx):
    n = ctx.summary.span_count.get("bench.data", 0)
    if not n:
        return None
    return ctx.summary.span_s["bench.data"] / n * 1e3
