"""``indexed_votes`` (kernels/indexed.py) against its roofline (%): the
least time of its calls in the window — the int32 position matrix read
once per call plus each padded row's literals and votes, and the MXU work
against the bf16 peak (``work.indexed_votes``) — over the kernel's device
time."""

KERNEL = r"^%indexed_votes(\.\d+)? = "


def read(ctx):
    k = ctx.trace.op(KERNEL)
    rows = ctx.counters.get("rows_padded")
    if k is None or not rows or k[0] <= 0:
        return None
    seconds, calls = k
    tm = ctx.tm
    m, n, L = tm["n_classes"], tm["n_clauses"], 2 * tm["n_features"]
    _, per_call = ctx.work.indexed_votes(m, n, L, 0)
    ops, per_rows = ctx.work.indexed_votes(m, n, L, rows)
    nbytes = calls * per_call + (per_rows - per_call)
    least = ctx.work.least_time_s(ops, nbytes,
                                  ctx.peaks["bf16_flops_per_s"],
                                  ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
