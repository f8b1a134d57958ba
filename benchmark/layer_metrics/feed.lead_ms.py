"""feed.lead_ms: How long a staged input waited, in the prefetcher's queue and then in the device's, before the chip began it: median over the window's dispatches of (device start of the step module's k-th run) - (end of h2d.stage with the seq of the k-th dispatch.megastep). It falls to 0 as the host becomes the limit."""

import statistics

from harness import program_trace


def read(ctx):
    leads = program_trace.window_leads(ctx)
    if not leads:
        return None
    return 1e3 * statistics.median(ld["lead_s"] for ld in leads)
