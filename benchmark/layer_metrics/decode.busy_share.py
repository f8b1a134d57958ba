"""decode.busy_share: Share of the window the shard-decode thread spent reading and decoding Parquet (ParquetStream stats)."""


def read(ctx):
    if ctx["job"] != "stream":
        return None
    w = ctx["window"]
    return 100.0 * w["stats"]["decode_seconds"] / w["seconds"]
