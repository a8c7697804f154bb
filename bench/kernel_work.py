"""Work of the kernels, from what they must touch, and the chip's peaks.

A kernel's least time is the larger of its operations over the peak rate
and its bytes over the memory bandwidth.  The bytes are counted from the
work, not from the kernel's layout, so a dense and a sparse kernel doing
the same expansion are held to the same least time, and no rewrite can
push a share past 100%.

``bitset_matmul`` (one phase-2 round of one label class in one direction)
ORs, for each of the ``lanes`` carrier columns, the frontier words of a
vertex's neighbours along that class's edges.  It must read the carrier
(4 bytes per vertex per lane), write the result (as much again) and read
each edge of the class once (source and destination, 4 bytes each).  The
calls of one round sweep every class once, so ``calls / classes`` sweeps
read every edge once.  Its operations are int32 ANDs and ORs on the VPU,
which no published peak bounds (the int8 and bf16 peaks are the MXU's),
so the share is bound by memory: least time = bytes / HBM bandwidth.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peak table's row for a device; an unknown device is an error."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def bitset_matmul_bytes(calls: int, n_vertices: int, lanes: int,
                        n_edges: int, classes: int) -> float:
    """Bytes ``calls`` calls of the phase-2 class expansion must move."""
    carrier = 2 * 4 * n_vertices * lanes
    return calls * carrier + (calls / classes) * 8 * n_edges


def roofline_share(work_bytes: float, seconds: float,
                   device_kind: str) -> float:
    """Least time for ``work_bytes`` at the HBM peak over the time taken,
    in percent."""
    least = work_bytes / peaks(device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / seconds
