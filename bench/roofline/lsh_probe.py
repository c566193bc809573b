"""The banded-LSH bucket probes (``kernels/lsh_probe.py``).

Each call compares a (Q, B) block of query keys with every row of a
(C, B) key matrix (full-lake probe and digest scan) or of a per-query
(Q, C', B) gathered block (fine probe), and writes a (Q, C) int32 hit
mask. At the least, a call reads each operand once and writes its mask
once, and makes Q * C * B uint32 compares. The trace's op name is the
HLO instruction, which carries the output and operand shapes.
"""
from __future__ import annotations

import math
import re

KERNELS = ("%lsh_probe_pallas", "%lsh_probe_gathered_pallas")
_BYTES = {"u8": 1, "s8": 1, "bf16": 2, "f16": 2, "u16": 2, "s16": 2,
          "u32": 4, "s32": 4, "f32": 4, "u64": 8, "s64": 8, "f64": 8}
_SHAPE = re.compile(r"\b([usf]\d+|bf16)\[([\d,]*)\]")


def match(op_name: str) -> bool:
    return op_name.startswith(KERNELS) and "tpu_custom_call" in op_name


def shapes(op_name: str):
    """(output, [operands]) as (dtype, dims) pairs."""
    lhs, rhs = op_name.split("custom-call(", 1)
    out = _SHAPE.search(lhs)
    operands = _SHAPE.findall(rhs.split("custom_call_target", 1)[0])

    def parse(m):
        dt, dims = m
        return dt, tuple(int(d) for d in dims.split(",") if d)
    return parse(out.groups()), [parse(o) for o in operands]


def nbytes(shape) -> int:
    dt, dims = shape
    return _BYTES[dt] * math.prod(dims)


def work(op_name: str) -> tuple[float, float]:
    """(compares, bytes) of one call."""
    out, operands = shapes(op_name)
    bands = operands[0][1][-1]
    compares = math.prod(out[1]) * bands
    return float(compares), float(sum(nbytes(o) for o in operands)
                                  + nbytes(out))


def least_seconds(op_name: str, peaks: dict) -> float:
    """Larger of compares at the int8 peak and bytes at HBM bandwidth.
    The probes are bound by bytes by two orders of magnitude: the chip's
    peak rate of uint32 compares is not published, and the int8 peak is an
    upper bound on it."""
    ops, by = work(op_name)
    return max(ops / peaks["int8_ops"], by / peaks["hbm_bytes_per_s"])
