"""train.compiles_in_window: XLA compiles the program counted inside the measured window (CompileWatch): none belong there."""


def read(ctx):
    if ctx["job"] != "stream":
        return None
    return float(ctx["window"]["compiles"])
