"""mf.item_fold: Item slots a step over the distinct item rows it reads and writes: the window's steps x batch over the step's own counter train.mf_item_rows (registry, summed on the device and folded with the loss). How much of the hot table's work the compact sum absorbs: the slots are summed in fast memory, only the distinct rows cross to HBM."""


def read(ctx):
    w = ctx["window"]
    if ctx.get("job") != "stream" or not w.get("mf_item_rows") \
            or not w.get("steps"):
        return None
    return w["steps"] * w["batch"] / w["mf_item_rows"]
