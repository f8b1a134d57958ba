"""Small things every job kind needs: files, the device, the clock."""

from __future__ import annotations

import importlib.util
import json
import os
import time
from typing import Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
RUN_DIR = os.path.join(BENCH_DIR, ".run")        # git-ignored scratch

now = time.perf_counter


def compile_cache_here() -> None:
    """One compile cache at a fixed place in the checkout, for the program
    too (its enable_compile_cache() takes this variable); sub-second
    compiles are kept, so that only a checkout's first run compiles."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file by path: readers and references are found by the
    names in BENCHMARK.json and the configuration, not by an import
    list someone has to edit."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family_module(kind: str, family: str):
    """benchmark/<kind>/<family>.py, imported as a package member so it can
    use its siblings (`reference.common`)."""
    import importlib
    import sys
    if BENCH_DIR not in sys.path:
        sys.path.insert(0, BENCH_DIR)
    return importlib.import_module(f"{kind}.{family}")


def seed31(seed: int) -> int:
    """The program's `-seed` option and JAX's PRNGKey take 31 bits; the
    driver's seeds can be larger."""
    return int(seed) % (2 ** 31 - 1)


def merge(base: dict, over: Optional[dict]) -> dict:
    """`over` laid on `base`, group by group (the toy overrides)."""
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = merge(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip (0 where the backend does not
    say, as on the CPU)."""
    import jax
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       "benchmark/peaks.json")
    return table[kind]


class SpanClock:
    """Carries the program tracer's spans (wall-clock microseconds in its
    Chrome export) onto perf_counter."""

    def __init__(self):
        self.wall_minus_perf = time.time() - time.perf_counter()

    def spans(self, tracer, t0: float, t1: float):
        """[(name, start_s, dur_s)] of the spans that end inside [t0, t1]."""
        out = []
        for ev in tracer.chrome_dict()["traceEvents"]:
            if ev.get("ph") != "X":
                continue
            s = ev["ts"] * 1e-6 - self.wall_minus_perf
            d = ev["dur"] * 1e-6
            if s + d >= t0 and s + d <= t1:
                out.append((ev["name"], s, d))
        return out
