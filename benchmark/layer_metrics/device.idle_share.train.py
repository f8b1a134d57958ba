"""device.idle_share.train: Share of the traced training window with no operation running on the device."""


def read(ctx):
    if ctx["job"] != "stream" or not ctx["trace"]:
        return None
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
