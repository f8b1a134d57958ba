"""step.gather_ms: Device time of one step in the table-row gather: seconds of the window's device operations whose innermost phase scope is hm.gather (the trace's own HLO names each operation's scope), over the window's steps."""

from harness import program_trace


def read(ctx):
    return program_trace.phase_ms(ctx, "hm.gather")
