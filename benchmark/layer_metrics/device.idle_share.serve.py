"""device.idle_share.serve: Share of the traced serving window with no operation running on the device."""


def read(ctx):
    if ctx["job"] != "predict_open_loop" or not ctx["trace"]:
        return None
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
