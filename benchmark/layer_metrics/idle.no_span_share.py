"""idle.no_span_share: Share of the traced window's idle seconds (gaps between device operations) that no span of the program covers."""


def read(ctx):
    if ctx["job"] != "stream" or not ctx["trace"] \
            or not ctx["trace"]["idle_gaps"]:
        return None
    gaps = dict(ctx["trace"]["idle_gaps"])
    return 100.0 * gaps.get("no_span", 0.0) / sum(gaps.values())
