"""``clause_outputs`` (kernels/clause_eval.py) on one clause shard against
its roofline (%): each call's bytes are those of the chip's own clause rows
(``work.clause_outputs`` at ``counters["clauses_per_chip"]``: the shard's
packed include words and one sample's packed literals read, one byte per
clause written) at one chip's HBM peak, over the kernel's device time in
the window. The trace sums the calls and seconds of every chip, so the
share is that of an average chip. None where the kernel did not run or the
driver counts no clauses per chip."""

KERNEL = r"^%clause_outputs\w*(\.\d+)? = "


def read(ctx):
    k = ctx.trace.op(KERNEL)
    n_local = ctx.counters.get("clauses_per_chip")
    if k is None or k[0] <= 0 or not n_local:
        return None
    seconds, calls = k
    _, nbytes = ctx.work.clause_outputs(n_local, 2 * ctx.tm["n_features"])
    return 100.0 * calls * nbytes / ctx.peaks["hbm_bytes_per_s"] / seconds
