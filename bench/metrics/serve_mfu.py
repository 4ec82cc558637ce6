"""The scores step's share of the chip's int8 peak (%): the dense work of
the rows served in the window (every literal test of every clause, as
multiply-accumulates; ``work.serve_ops``) over the device time of the
scores executables. The count is the same whichever engine does the work,
so it bounds a gain after a kernel leaves the path."""

SCORES = r"^jit_bundle_scores\b"


def read(ctx):
    t = ctx.trace.module(SCORES)
    rows = ctx.counters.get("rows_real")
    if t is None or not rows or t[0] <= 0:
        return None
    tm = ctx.tm
    ops = ctx.work.serve_ops(rows, tm["n_classes"], tm["n_clauses"],
                             2 * tm["n_features"])
    return 100.0 * ops / (t[0] * ctx.peaks["int8_ops_per_s"])
