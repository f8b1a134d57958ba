"""dispatch.host_ms: Host time of one dispatch: mean length of the dispatch.megastep / dispatch.step spans."""


def read(ctx):
    if ctx["job"] != "stream":
        return None
    durs = [d for n, _, d in ctx["spans"]
            if n in ("dispatch.megastep", "dispatch.step")]
    return 1e3 * sum(durs) / len(durs) if durs else None
