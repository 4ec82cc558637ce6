"""The clause-sharded train step's share of its chips' int8 peak (%):
samples per second in the traced window times the dense work of one
sequential sample over the whole model (two class rounds, each evaluating
and updating every TA of its class row; ``work.train_ops_per_sample``),
over the chips the model is sharded across (``counters["chips"]``) times
one chip's peak. None where the driver counts no chips."""


def read(ctx):
    samples, chips = ctx.counters.get("samples"), ctx.counters.get("chips")
    if not samples or not chips:
        return None
    tm = ctx.tm
    ops = ctx.work.train_ops_per_sample(tm["n_clauses"],
                                        2 * tm["n_features"])
    rate = samples / ctx.counters["window_s"]
    return 100.0 * rate * ops / (chips * ctx.peaks["int8_ops_per_s"])
