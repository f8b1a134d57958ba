"""pairs.track_ms: Mean length of pairs.track, one batch's observed (feature, field) pairs made unique and merged on FFM's pairs-track thread, over the batches tracked in the window."""

from harness import feed_trace


def read(ctx):
    return feed_trace.pairs_track_ms(ctx)
