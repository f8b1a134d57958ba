"""Native MIX server wrapper — builds and supervises native/mix_server.cpp.

Reference: hivemall.mix.server.MixServer runs as a standalone native-code
(JVM/Netty) process started by `mixserv`; SURVEY.md §3.16/§4.3 demands a
native-runtime equivalent here, not only the asyncio implementation. The
C++ server speaks the SAME length-prefixed MixMessage wire protocol, so
`hivemall_tpu.parallel.mix_service.MixClient` (and trainers' `-mix`)
connect to either implementation unchanged. TLS and fault injection stay
on the Python server (they are test/ops tooling); this is the in-cluster
plaintext data path.

Build-on-first-use like utils/native.py: `g++ -O3` into a source-hash-keyed
native/mix_server_native-<sha> next to the source; environments without a
toolchain fall back to the Python server (start() raises with a clear
message; `mixserv --impl auto` handles the fallback).
"""

from __future__ import annotations

import os
import subprocess
import sys
from typing import Optional

_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_SRC = os.path.join(_DIR, "mix_server.cpp")

__all__ = ["NativeMixServer", "native_available", "build_native_server"]


def build_native_server() -> Optional[str]:
    """Path to the server binary, building it if needed; None if the
    toolchain or source is unavailable (callers fall back to the asyncio
    server). Shares utils.native's build-on-first-use helper and the
    single HIVEMALL_TPU_NO_NATIVE=1 switch."""
    from ..utils.native import build_artifact

    return build_artifact(_SRC, "mix_server_native", "", [])


def native_available() -> bool:
    return build_native_server() is not None


class NativeMixServer:
    """Subprocess supervisor with the same start()/stop()/port surface as
    mix_service.MixServer, so tests and `mixserv` treat the two
    implementations interchangeably."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0):
        self.host = host
        self.port = port
        self._proc: Optional[subprocess.Popen] = None

    def start(self) -> "NativeMixServer":
        binpath = build_native_server()
        if binpath is None:
            raise RuntimeError(
                "native mix server unavailable (no g++ toolchain or "
                "HIVEMALL_TPU_NO_NATIVE=1); use mix_service.MixServer")
        self._proc = subprocess.Popen(
            [binpath, "--host", self.host, "--port", str(self.port)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        line = self._proc.stdout.readline().strip()
        if not line.startswith("PORT "):
            try:
                _, err = self._proc.communicate(timeout=5)
            except subprocess.TimeoutExpired:
                err = ""
            self.stop()
            raise RuntimeError(
                "native mix server failed to bind: "
                f"{(err or line).strip() or 'no output'!r}")
        self.port = int(line.split()[1])
        return self

    def stop(self) -> None:
        if self._proc is not None:
            self._proc.terminate()
            try:
                self._proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                self._proc.kill()
                self._proc.wait(timeout=5)
            self._proc = None

    def __enter__(self) -> "NativeMixServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


def main(argv=None) -> int:
    """`python -m hivemall_tpu.parallel.mix_native --port N` — run the
    native server in the foreground (the mixserv CLI's --impl native)."""
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=11212)
    args = ap.parse_args(argv)
    binpath = build_native_server()
    if binpath is None:
        print("native mix server unavailable", file=sys.stderr)
        return 1
    proc = subprocess.Popen([binpath, "--host", args.host,
                             "--port", str(args.port)])
    try:
        return proc.wait()
    except KeyboardInterrupt:
        proc.terminate()
        return 0


if __name__ == "__main__":
    raise SystemExit(main())
