"""setup.data_s.serve: Seconds of set-up spent generating rows, shards and the request bodies."""


def read(ctx):
    if ctx["job"] != "predict_open_loop":
        return None
    return float(ctx["timings"]["data_s"])
