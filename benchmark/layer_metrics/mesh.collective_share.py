"""mesh.collective_share: Share of device operation time in collectives (all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all)."""


def read(ctx):
    if ctx["job"] != "stream" or not ctx["trace"] \
            or ctx["trace"]["n_planes"] < 2:
        return None
    kinds = ("all-reduce", "all-gather", "reduce-scatter",
             "collective-permute", "all-to-all")
    ops = ctx["trace"]["ops"]
    total = sum(ops.values())
    coll = sum(s for n, s in ops.items() if n.startswith(kinds))
    return 100.0 * coll / total if total else None
