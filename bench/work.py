"""The work an operator needs, counted from its shapes alone.

A roofline share divides the least time this work can take on the chip by
the time the implementation took.  The counts here are the operator's own
work, never a kernel's: an implementation that reads A twice, or spends
MXU passes on a one-hot matrix, is held to the same numbers.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_PATH = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str, path: Path = PEAKS_PATH) -> dict:
    """The peak table of ``device_kind``; an unknown device is an error."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {path}; have "
            f"{sorted(table)}"
        )
    return table[device_kind]


def countsketch_apply(m: int, cols: int, d: int, itemsize: int = 4) -> dict:
    """A CountSketch S (d x m) applied to an m x ``cols`` operand X.

    Bytes: X read once, the bucket row (int32) and the sign row (itemsize)
    read once, S X (d x cols) written once.  Operations: one signed add per
    entry of X.
    """
    return {
        "flops": m * cols,
        "bytes": m * cols * itemsize + m * 4 + m * itemsize
        + d * cols * itemsize,
    }


def least_seconds(work: dict, peak: dict) -> tuple[float, str]:
    """The least time ``work`` takes at ``peak``, and which bound sets it.

    Operations are held to the bf16 MXU peak, the chip's highest, so the
    bound is never above what any precision could reach.
    """
    t_ops = work["flops"] / peak["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_bytes, "hbm") if t_bytes >= t_ops else (t_ops, "ops")
