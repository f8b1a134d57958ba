"""loadgen.late_ms: 95th percentile of how late the generator sent a request after it was due."""


def read(ctx):
    if ctx["job"] != "predict_open_loop" or not ctx["loadgen"]["late_ms"]:
        return None
    late = sorted(ctx["loadgen"]["late_ms"])
    return late[min(len(late) - 1, int(0.95 * len(late)))]
