"""step.unscoped_share: Share of the window's device-operation seconds that carry no phase scope (hm.scan alone, or none: operations the compiler made itself): how much of the step the four phase metrics do not explain."""

from harness import program_trace


def read(ctx):
    secs = program_trace.phase_seconds(ctx)
    return 100.0 * secs["unscoped"] / sum(secs.values()) if secs else None
