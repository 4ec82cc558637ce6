"""``ta_update`` (kernels/ta_update.py) against its roofline (%): each
call's bytes (the class row's int16 TAs read and written, its float32
uniforms read; ``work.ta_update``) at the HBM peak, over the kernel's
device time in the window."""

KERNEL = r"^%ta_update(\.\d+)? = "


def read(ctx):
    k = ctx.trace.op(KERNEL)
    if k is None or k[0] <= 0:
        return None
    seconds, calls = k
    tm = ctx.tm
    _, nbytes = ctx.work.ta_update(tm["n_clauses"], 2 * tm["n_features"])
    return 100.0 * calls * nbytes / ctx.peaks["hbm_bytes_per_s"] / seconds
