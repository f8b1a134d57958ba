"""setup.construct_s.train: Seconds of set-up spent constructing the trainer: its tables and AdaGrad state drawn from the seed. Where the configuration says `construct_on: host`, this is the detour over the host's CPU backend and the copy to the chip (PERF.md section 7, first)."""


def read(ctx):
    if ctx["job"] != "stream":
        return None
    return float(ctx["timings"]["construct_s"])
