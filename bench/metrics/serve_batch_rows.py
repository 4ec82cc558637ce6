"""Mean real rows per batch the server dispatched in the window, from
``AsyncTMServer.stats()`` (``rows_real`` over ``batches``)."""


def read(ctx):
    batches = ctx.counters.get("batches")
    if not batches:
        return None
    return ctx.counters["rows_real"] / batches
