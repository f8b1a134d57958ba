"""mf.user_rows: Distinct rows of the user table a step reads and writes, the mean over the window's steps: the step's own counter train.mf_user_rows (registry, summed on the device and folded with the loss) over the steps. The cold table's share of the distinct-row tail: each such row is a row copy in and out of HBM for the table and for AdaGrad's sums."""


def read(ctx):
    w = ctx["window"]
    if ctx.get("job") != "stream" or not w.get("mf_user_rows") \
            or not w.get("steps"):
        return None
    return w["mf_user_rows"] / w["steps"]
