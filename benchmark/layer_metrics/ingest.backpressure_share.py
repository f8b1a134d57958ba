"""ingest.backpressure_share: Share of the window the ingest submitter was blocked on a full queue: the pipeline downstream of prep, not prep, sets the pace."""


def read(ctx):
    if ctx["job"] != "stream":
        return None
    w = ctx["window"]
    return 100.0 * w["stats"]["prep_backpressure_seconds"] / w["seconds"]
