"""Device time in all-reduce operations, in ms per train step per chip.

All-reduces are found by their HLO op kind in the trace's instruction text
(``all-reduce``, or the ``-start`` / ``-done`` halves of an asynchronous
one), whatever the compiler names the instruction. In the clause-sharded
sync step they are the vote psum of each class round (two per sample,
scope ``tm.votes``) and the step's one overflow psum. The device seconds of
every chip are summed by the trace, so they are divided by the number of
devices and by the window's steps. None where no all-reduce ran."""

KIND = r"= .*\ball-reduce(-start|-done)?\("


def read(ctx):
    k = ctx.trace.op(KIND)
    steps = ctx.counters.get("steps")
    if k is None or not steps:
        return None
    seconds, _ = k
    return 1e3 * seconds / ctx.trace.n_devices / steps
