"""step.mfu: The whole step against the chip: the least time the work of one step needs (benchmark/work, bytes and FLOPs of the published equations, against benchmark/peaks.json) over the device time one step took."""


def read(ctx):
    if ctx["job"] != "stream" or not ctx["trace"] or not ctx["peaks"] \
            or not ctx["window"]["steps"] or not ctx["trace"]["busy_s"]:
        return None
    from work.common import least_seconds
    chips = max(1, ctx["trace"]["n_planes"])
    work = ctx["work"].train_step(ctx["cfg"], ctx["window"]["batch"])
    per_step = ctx["trace"]["busy_s"] / ctx["window"]["steps"]
    return 100.0 * least_seconds(work, ctx["peaks"]) / chips / per_step
