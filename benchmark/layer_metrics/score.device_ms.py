"""score.device_ms: Device time of one scored batch: seconds with an operation running on the device over the batches the traced window scored."""


def read(ctx):
    if ctx["job"] != "predict_open_loop" or not ctx["trace"] \
            or not ctx["batcher"]["batches"]:
        return None
    return 1e3 * ctx["trace"]["busy_s"] / ctx["batcher"]["batches"]
