"""The train step's share of the chip's int8 peak (%): samples per second
in the traced window times the dense work of one sequential sample (two
class rounds, each evaluating and updating every TA of its class row;
``work.train_ops_per_sample``). The count is the same whichever kernels do
the work, so it bounds a gain after a kernel leaves the path."""


def read(ctx):
    samples = ctx.counters.get("samples")
    if not samples:
        return None
    tm = ctx.tm
    ops = ctx.work.train_ops_per_sample(tm["n_clauses"],
                                        2 * tm["n_features"])
    rate = samples / ctx.counters["window_s"]
    return 100.0 * rate * ops / ctx.peaks["int8_ops_per_s"]
