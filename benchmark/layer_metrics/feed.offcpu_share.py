"""feed.offcpu_share: On the thread feed.cycle_ms chose, 100 x (1 - thread-CPU seconds / wall seconds) over its top-level work spans, h2d.stage left out (it blocks on its transfer by design): how much of its work's wall time the thread was waiting, for the GIL, a page fault or a blocking call, and not computing."""

from harness import feed_trace


def read(ctx):
    return feed_trace.offcpu_share(ctx)
