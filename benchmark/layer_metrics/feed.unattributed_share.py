"""feed.unattributed_share: On the busiest LOOP thread of the feed, the share of its busy time that no span of that thread covers: (window - wait spans - top-level work spans) over (window - wait spans). The program's own Python between spans, or the thread waiting for the GIL there."""

from harness import feed_trace


def read(ctx):
    return feed_trace.unattributed_share(ctx)
