"""The forest pass's work from the algorithm, not from the kernel.

For one flush the algorithm reads every row's features once, makes one
comparison per tree and level, adds one leaf value per tree, writes one
score per row, and reads each model the flush touches once: its tested
features and thresholds (``T x D`` of each) and its leaves (``T x 2^D``).
The padded tile layout and the one-hot matrix products of the kernel that
computes it are not counted, so a rewrite of the kernel leaves the count
as it is."""

from __future__ import annotations

import json
import pathlib

F32 = 4
PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def flush_work(rows: int, segments: int, *, n_trees: int, depth: int,
               n_features: int) -> tuple[int, int]:
    """(operations, bytes) of one flush of ``rows`` rows over ``segments``
    models of ``n_trees`` trees of depth ``depth``."""
    ops = rows * n_trees * (depth + 1)                 # compares + leaf adds
    row_bytes = rows * (n_features + 1) * F32          # features in, score out
    model_bytes = segments * n_trees * (2 * depth + (1 << depth)) * F32
    return ops, row_bytes + model_bytes


def peaks(device_kind: str, path: pathlib.Path = PEAKS) -> dict:
    """The published peaks of ``device_kind``; a kind not in the table is an
    error, never a default."""
    table = json.loads(pathlib.Path(path).read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {path}")
    return table[device_kind]


def roofline_pct(ops: float, nbytes: float, kernel_s: float,
                 peak: dict) -> tuple[float, str]:
    """Share of the least time the chip could take, and which bound sets
    that time (``"compute"`` or ``"memory"``)."""
    t_ops = ops / peak["flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    bound = "compute" if t_ops >= t_mem else "memory"
    return 100.0 * max(t_ops, t_mem) / kernel_s, bound
