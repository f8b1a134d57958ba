"""loop.wait_input_share: Share of the window the trainer's thread spent waiting for its next staged input (span loop.wait_input)."""


def read(ctx):
    if ctx["job"] != "stream":
        return None
    waits = [d for n, _, d in ctx["spans"] if n == "loop.wait_input"]
    return 100.0 * sum(waits) / ctx["window"]["seconds"] if waits else None
