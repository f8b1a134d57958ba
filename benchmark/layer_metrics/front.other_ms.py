"""front.other_ms: Median of the residual hop (result pick-up, response build) from x-hivemall-hop."""


def read(ctx):
    if ctx["job"] != "predict_open_loop" or not ctx["hops"].get("other"):
        return None
    import statistics
    return statistics.median(ctx["hops"]["other"])
