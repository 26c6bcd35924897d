"""Bytes an operation has to move, from its sizes alone, and the least
time a chip could take for them.

The counts come from the operation, never from an implementation:
stored values (nnz) and vector lengths (n).  Indices, hole masks,
padding, diagonals stored as full lanes and temporaries are not
counted, so a kernel that stops reading them reads the same yardstick
and can never pass 100%.  Inside a solver's loop, the vectors count
nothing: at these sizes the compiler keeps the loop's vectors in the
chip's on-chip memory (the ``S(1)`` memory space of the ``while``
operands in the trace), and a count of 24 n bytes of vector traffic an
iteration read 101% of the roofline on the v5e.  What every iteration
must stream from HBM is the operator's stored values.

A stored value takes two operations (multiply and add); at
2 / itemsize operations per byte these kernels sit far below the
chip's ridge point, so their rooflines are bounds on bytes alone.
"""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def spmv_bytes(n: int, nnz: int, itemsize: int) -> int:
    """One eager ``y = A x`` with x and y in HBM: read each stored
    value and x, write y."""
    return itemsize * (nnz + 2 * n)


def resident_spmv_bytes(nnz: int, itemsize: int) -> int:
    """One ``A x`` inside a solver's loop, whose vectors may stay on
    the chip: read each stored value."""
    return itemsize * nnz


def cg_iteration_bytes(nnz: int, itemsize: int) -> int:
    """One unpreconditioned CG iteration: its one product streams the
    operator's stored values; the vector updates and dot products may
    run on the chip."""
    return resident_spmv_bytes(nnz, itemsize)


def spmv_flops(nnz: int) -> int:
    return 2 * nnz


def peak(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; a chip that
    is not in ``peaks.json`` is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in peaks.json")
    return table[device_kind]


def least_seconds(nbytes: float, flops: float, chips: int,
                  device_kind: str) -> float:
    """The larger of bytes over HBM bandwidth and operations over the
    compute peak, for work spread evenly over ``chips`` chips."""
    p = peak(device_kind)
    return max(nbytes / (chips * p["hbm_bytes_per_s"]),
               flops / (chips * p["bf16_flops_per_s"]))


def share(least_s: float, measured_s: float) -> float:
    """Roofline share in percent."""
    return 100.0 * least_s / measured_s
