"""ingest.prep_load: How busy the ingest pool was: summed in-worker prep seconds over workers x window (PipelineStats)."""


def read(ctx):
    if ctx["job"] != "stream":
        return None
    w = ctx["window"]
    return 100.0 * w["stats"]["prep_seconds"] / (max(1, w["workers"])
                                                 * w["seconds"])
