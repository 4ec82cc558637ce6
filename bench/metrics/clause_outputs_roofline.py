"""``clause_outputs`` (kernels/clause_eval.py) against its roofline (%):
each call's bytes (the class row's packed include words and one sample's
packed literals read, one byte per clause written; ``work.clause_outputs``)
at the HBM peak, over the kernel's device time in the window."""

KERNEL = r"^%clause_outputs\w*(\.\d+)? = "


def read(ctx):
    k = ctx.trace.op(KERNEL)
    if k is None or k[0] <= 0:
        return None
    seconds, calls = k
    tm = ctx.tm
    _, nbytes = ctx.work.clause_outputs(tm["n_clauses"],
                                        2 * tm["n_features"])
    return 100.0 * calls * nbytes / ctx.peaks["hbm_bytes_per_s"] / seconds
