"""step.device_ms: Device time of one minibatch step: seconds with an operation running on the device (profiler trace) over the steps the window ran."""


def read(ctx):
    if ctx["job"] != "stream" or not ctx["trace"] \
            or not ctx["window"]["steps"]:
        return None
    return 1e3 * ctx["trace"]["busy_s"] / ctx["window"]["steps"]
