"""source.serial_share: Share of the window the single source thread spent assembling batches (shuffle, row gather, padding): time inside the stream iterator less its wait for a decoded shard."""


def read(ctx):
    if ctx["job"] != "stream":
        return None
    w = ctx["window"]
    busy = w["stats"]["source_seconds"] - w["stats"]["decode_wait_seconds"]
    return 100.0 * max(busy, 0.0) / w["seconds"]
