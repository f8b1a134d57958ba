"""stage.h2d_share: Share of the window spent stacking megabatches and in host-to-device transfers (spans stager.stack + h2d.stage)."""


def read(ctx):
    if ctx["job"] != "stream" or not ctx["spans"]:
        return None
    busy = sum(d for n, _, d in ctx["spans"]
               if n in ("stager.stack", "h2d.stage"))
    return 100.0 * busy / ctx["window"]["seconds"]
