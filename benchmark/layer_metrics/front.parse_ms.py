"""front.parse_ms: Median of the parse hop (read body, JSON, hash) from x-hivemall-hop."""


def read(ctx):
    if ctx["job"] != "predict_open_loop" or not ctx["hops"].get("parse"):
        return None
    import statistics
    return statistics.median(ctx["hops"]["parse"])
