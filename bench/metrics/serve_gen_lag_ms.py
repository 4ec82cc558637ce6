"""How late the load generator submitted: the 95th percentile, over the
window's requests, of submit time minus due time (host clock), in ms."""


def read(ctx):
    return ctx.counters.get("gen_lag_p95_ms")
