"""score.mfu: Scoring against the chip: the least time the rows scored in the window need (rows read only; benchmark/work against benchmark/peaks.json) over the device time they took."""


def read(ctx):
    if ctx["job"] != "predict_open_loop" or not ctx["trace"] \
            or not ctx["peaks"] or not ctx["trace"]["busy_s"] \
            or not ctx["batcher"]["rows"]:
        return None
    from work.common import least_seconds
    work = ctx["work"].score(ctx["cfg"], ctx["batcher"]["rows"])
    return 100.0 * least_seconds(work, ctx["peaks"]) / ctx["trace"]["busy_s"]
