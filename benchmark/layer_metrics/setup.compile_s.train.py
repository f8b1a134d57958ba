"""setup.compile_s.train: Seconds of XLA compilation inside set-up (CompileWatch); near zero once the compile cache is warm."""


def read(ctx):
    if ctx["job"] != "stream":
        return None
    return float(ctx["timings"]["compile_s"])
