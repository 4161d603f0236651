"""Least time of the sketched Sinkhorn iteration on a chip, from the work
the algorithm needs, whatever implements it.

Per iteration there are two half-steps. Each half-step, for every live
sketch entry, reads 4 bytes each of its row index, its column index, its
log-value and the potential it gathers, and does 5 operations on it (add
the gathered potential, compare against the running max, subtract the
max, exponentiate, accumulate); it reads the other side's potential
vector and writes its own once. The count uses the live ``nnz``, not the
sketch's padded capacity, so a loop that stops touching dead slots shows
as a higher share, not as less work.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
ENTRY_BYTES = 4 * 4
ENTRY_OPS = 5
WORD = 4


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The chip's peaks; an unknown device kind is an error."""
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def iteration_work(nnz: int, n: int, m: int) -> tuple[float, float]:
    """``(bytes, operations)`` of one iteration (two half-steps)."""
    bytes_ = 2 * ENTRY_BYTES * nnz + 2 * WORD * (n + m)
    return float(bytes_), float(2 * ENTRY_OPS * nnz)


def least_time(nnz: int, n: int, m: int, iterations: int, device_kind: str) -> tuple[float, str]:
    """``(seconds, bound)``: the larger of the bandwidth bound and the
    compute bound of ``iterations`` iterations, and which one it is."""
    pk = peaks(device_kind)
    b, ops = iteration_work(nnz, n, m)
    t_mem = iterations * b / pk["hbm_bytes_per_s"]
    t_ops = iterations * ops / pk["flops_bf16"]
    return (t_mem, "bandwidth") if t_mem >= t_ops else (t_ops, "compute")
