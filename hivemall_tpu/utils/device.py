"""Device policy: where Pallas may be interpreted, and which device a
child process gets.

A chip belongs to one process at a time. A process that has initialised
JAX on the TPU holds it, and a child that then asks for it dies in
backend init ("libtpu multi-process lockfile"), which callers used to
swallow into unrelated errors. So every launcher states its children's
device through this module instead of inheriting one by accident: a named
chip (:func:`chip_env`), the host CPU on purpose (:data:`CPU_ENV`), or a
refusal (:class:`DevicePolicyError`).
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List

__all__ = ["DevicePolicyError", "CPU_ENV", "cpu_requested",
           "pallas_interpret", "holds_accelerator", "visible_chip_count",
           "chip_env", "child_device_envs", "names_device", "child_env",
           "host_only_worker"]


class DevicePolicyError(RuntimeError):
    """The requested process/device layout cannot work on this host."""


# a child that runs on the host CPU because its launcher said so
CPU_ENV: Dict[str, str] = {"JAX_PLATFORMS": "cpu"}

# env keys that state a child's device; an overlay carrying one is explicit
_DEVICE_KEYS = ("JAX_PLATFORMS", "TPU_VISIBLE_CHIPS")


def cpu_requested() -> bool:
    """True when the CPU platform was asked for BY NAME — JAX_PLATFORMS=cpu
    in the environment (tests/conftest.py, run_tests.sh) or the equivalent
    jax.config setting. ``tpu,cpu`` (the chip machine's setting) is a
    request for the TPU."""
    import jax
    want = jax.config.jax_platforms or ""
    return want.split(",")[0].strip().lower() == "cpu"


def pallas_interpret() -> bool:
    """The one interpret-mode policy for every ``pallas_call`` in the repo.

    Kernels compile (False) on a TPU. The interpreter (True) is something
    a CPU run asks for by naming the CPU platform; a backend JAX fell back
    to on its own raises, so a run on the chip machine can never slide
    into interpret mode because JAX found no TPU."""
    import jax
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu" and cpu_requested():
        return True
    raise DevicePolicyError(
        f"Pallas kernels need a TPU, but JAX is running on {backend!r} "
        f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}). Set "
        "JAX_PLATFORMS=cpu to run them in interpret mode on purpose.")


def holds_accelerator() -> bool:
    """Whether THIS process has initialised a non-CPU JAX backend — i.e.
    holds the chip(s), so no child can have one."""
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        return False
    import jax
    return jax.default_backend() != "cpu"


def visible_chip_count() -> int:
    """TPU chips on this host, counted WITHOUT initialising JAX (which
    would claim them): one ``/dev/vfio/<n>`` node per chip."""
    return sum(os.path.basename(p).isdigit()
               for p in glob.glob("/dev/vfio/*"))


def chip_env(i: int) -> Dict[str, str]:
    """Env overlay that gives a child process chip ``i`` of this host and
    nothing else (libtpu's one-process-per-chip recipe: each process is
    its own 1x1x1 slice with its own controller port)."""
    port = 8476 + int(i)
    return {"TPU_VISIBLE_CHIPS": str(int(i)),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_MESH_CONTROLLER_ADDRESS": f"localhost:{port}",
            "TPU_MESH_CONTROLLER_PORT": str(port)}


def names_device(env) -> bool:
    """Whether an env overlay states its process's device."""
    return any(k in (env or {}) for k in _DEVICE_KEYS)


def child_env(*overlays) -> Dict[str, str]:
    """This process's environment with ``overlays`` applied in order (a
    ``None`` value removes the key) — what a launcher hands to Popen."""
    env = dict(os.environ)
    for overlay in overlays:
        for k, v in (overlay or {}).items():
            if v is None:
                env.pop(k, None)
            else:
                env[k] = str(v)
    return env


def host_only_worker() -> None:
    """Process-pool initializer (runs before the worker imports jax): pool
    workers do host-side work and must never claim the chip their parent
    holds, so they get the CPU platform by name."""
    os.environ.update(CPU_ENV)


def child_device_envs(n: int) -> List[Dict[str, str]]:
    """One device env overlay per child, for ``n`` children that each run
    jitted code: the CPU when this process runs on the CPU by name,
    otherwise chip ``i`` for child ``i``. Refuses (no hang, no silent CPU)
    when this process already holds the chips or the host has fewer than
    ``n``."""
    if cpu_requested():
        return [dict(CPU_ENV) for _ in range(n)]
    if holds_accelerator():
        raise DevicePolicyError(
            "this process has initialised JAX on the accelerator and "
            "holds the chip, so a child process cannot get one. Launch "
            "the children from a process that has not touched JAX, or "
            "give them the host CPU on purpose (env={'JAX_PLATFORMS': "
            "'cpu'}).")
    chips = visible_chip_count()
    if n > chips:
        raise DevicePolicyError(
            f"{n} child processes need {n} chips (one process per chip) "
            f"but this host has {chips}. Start fewer, or run them on the "
            "host CPU on purpose with JAX_PLATFORMS=cpu.")
    return [chip_env(i) for i in range(n)]
