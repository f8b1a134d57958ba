"""batcher.mean_rows: Mean rows in a scored batch over the window (batcher counters)."""


def read(ctx):
    if ctx["job"] != "predict_open_loop" or not ctx["batcher"]["batches"]:
        return None
    return ctx["batcher"]["rows"] / ctx["batcher"]["batches"]
