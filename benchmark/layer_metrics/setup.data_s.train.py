"""setup.data_s.train: Seconds of set-up spent generating rows and writing the Parquet shards."""


def read(ctx):
    if ctx["job"] != "stream":
        return None
    return float(ctx["timings"]["data_s"])
