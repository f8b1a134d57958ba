"""setup.compile_s.serve: Seconds of XLA compilation inside set-up (CompileWatch); near zero once the compile cache is warm."""


def read(ctx):
    if ctx["job"] != "predict_open_loop":
        return None
    return float(ctx["timings"]["compile_s"])
