from __future__ import annotations

BYTES = {"float32": 4, "bfloat16": 2}


def least_seconds(work: dict, peaks: dict) -> float:
    """The least time the chip could take: the larger of bytes over peak
    bytes/s and FLOPs over peak FLOP/s."""
    return max(work["bytes"] / peaks["hbm_bytes_per_s"],
               work["flops"] / peaks["flops_per_s"])


def bound_by(work: dict, peaks: dict) -> str:
    return ("hbm" if work["bytes"] / peaks["hbm_bytes_per_s"]
            >= work["flops"] / peaks["flops_per_s"] else "flops")
