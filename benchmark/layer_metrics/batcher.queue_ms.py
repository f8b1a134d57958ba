"""batcher.queue_ms: Median of the queue hop (wait in the micro-batcher) from x-hivemall-hop."""


def read(ctx):
    if ctx["job"] != "predict_open_loop" or not ctx["hops"].get("queue"):
        return None
    import statistics
    return statistics.median(ctx["hops"]["queue"])
