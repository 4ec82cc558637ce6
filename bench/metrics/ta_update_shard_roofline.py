"""``ta_update`` (kernels/ta_update.py) on one clause shard against its
roofline (%): each call's bytes are those of the chip's own clause rows
(``work.ta_update`` at ``counters["clauses_per_chip"]``: the shard's int16
TAs read and written, its float32 uniforms read) at one chip's HBM peak,
over the kernel's device time in the window. The trace sums the calls and
seconds of every chip, so the share is that of an average chip. None where
the kernel did not run or the driver counts no clauses per chip."""

KERNEL = r"^%ta_update(\.\d+)? = "


def read(ctx):
    k = ctx.trace.op(KERNEL)
    n_local = ctx.counters.get("clauses_per_chip")
    if k is None or k[0] <= 0 or not n_local:
        return None
    seconds, calls = k
    _, nbytes = ctx.work.ta_update(n_local, 2 * ctx.tm["n_features"])
    return 100.0 * calls * nbytes / ctx.peaks["hbm_bytes_per_s"] / seconds
