"""step.dense_pass_share: Share of device operation time in operations that produce an array at least half the size of the whole logical table: passes over the table, not over the rows a batch touches."""


def read(ctx):
    if ctx["job"] != "stream" or not ctx["trace"] or not ctx["trace"]["ops"]:
        return None
    import re
    floor = ctx["work"].table_elements(ctx["cfg"]) // 2 // max(
        1, ctx["trace"]["n_planes"])
    dense = total = 0.0
    for name, secs in ctx["trace"]["ops"].items():
        total += secs
        m = re.search(r"\[([\d,]+)\]", name)
        if m:
            n = 1
            for d in m.group(1).split(","):
                n *= int(d)
            if n >= floor:
                dense += secs
    return 100.0 * dense / total if total else None
