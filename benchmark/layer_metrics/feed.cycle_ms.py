"""feed.cycle_ms: The busiest feed thread's busy milliseconds per dispatch, from inside the program: a loop thread's (h2d-prefetch, ingest-source) window less its wait spans, a service thread's (pq-decode, ingest_N, pairs-track) top-level spans, each over the dispatches of the stream that passed the thread in its window (by its spans' batch / seq ordinals or decoded rows); the largest over every thread but the dispatching one. Against steps_per_dispatch x step.device_ms: the cell turns host-bound when they meet."""

from harness import feed_trace


def read(ctx):
    return feed_trace.cycle_ms(ctx)
