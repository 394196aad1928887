"""Bytes and operations each Pallas kernel's algorithm needs for one call,
from the call's shapes, and the kernel's share of its roofline.

A kernel's calls are found among the trace's device operations by their
HLO text: a ``tpu_custom_call`` whose operand and result shapes match the
kernel's signature. Both kernels are bound by memory bandwidth: they need
about one operation per element they read, far below the chip's ridge
point (peak FLOP/s over peak bytes/s, about 240 on a v5e), so the least
time a call can take is its bytes over the peak bandwidth.

- ``segment_combine`` (``kernels/segment_combine.py``): operands are the
  two chunk tables, the sorted segment ids ``(..., E/L, R, L)`` and the
  values ``(..., D, E/L, R, L)``; one result ``(..., NB, D, BR)``. It needs
  to read every id and value once and write every output row once, and
  one combine per value.
- ``bucket_route`` (``kernels/bucket_route.py``, both entry points): one
  operand, the keys ``(..., M/L, R, L)``, or two with the lane masks
  ``(..., Q, M/L, R, L)``; results are the ranks (the keys' shape), the
  counts, and with lanes the per-lane counts. It needs to read every key
  (and lane bit, stored as int32) once and write every rank once, and one
  count per key and lane.
"""
from __future__ import annotations

import json
import math
import pathlib
import re
from typing import Iterable, List, Optional, Tuple

_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|f32|bf16|f16|s64|u64|f64)"
                    r"\[([0-9,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
          "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return table[device_kind]


def _shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in _SHAPE.findall(text)]


def _size(shape: Tuple[str, Tuple[int, ...]]) -> int:
    return _BYTES[shape[0]] * math.prod(shape[1])


def signature(hlo: str):
    """(results, operands) shapes of a ``tpu_custom_call`` instruction's
    text (the trace names each device operation by its HLO text), or None
    for any other instruction."""
    if 'custom_call_target="tpu_custom_call"' not in hlo:
        return None
    head, _, rest = hlo.partition(" custom-call(")
    operands, _, _ = rest.partition("), custom_call_target=")
    return _shapes(head.partition("=")[2]), _shapes(operands)


def segment_combine(results, operands) -> Optional[Tuple[int, int]]:
    """(bytes, ops) of one segment-combine call, or None if the shapes are
    not the kernel's."""
    if len(operands) != 4 or len(results) != 1:
        return None
    seg, vals = operands[2], operands[3]
    if seg[0] != "s32" or len(vals[1]) != len(seg[1]) + 1:
        return None
    n_vals = math.prod(vals[1])
    return _size(seg) + _size(vals) + _size(results[0]), n_vals


def bucket_route(results, operands) -> Optional[Tuple[int, int]]:
    """(bytes, ops) of one bucket-ranking call (with or without lanes)."""
    if len(operands) not in (1, 2) or len(results) != len(operands) + 1:
        return None
    keys = operands[0]
    if keys[0] != "s32" or results[0][1] != keys[1]:
        return None
    moved = _size(keys) + _size(results[0])
    ops = math.prod(keys[1])
    if len(operands) == 2:
        lanes = operands[1]
        moved += _size(lanes)
        ops += math.prod(lanes[1])
    return moved, ops


KERNELS = {"segment_combine": segment_combine, "bucket_route": bucket_route}


def calls(trace, kernel: str) -> Iterable[Tuple[float, int, int]]:
    """(seconds, bytes, ops) of every call of ``kernel`` in the trace."""
    match = KERNELS[kernel]
    for op in trace.ops():
        sig = signature(op.name)
        if sig is None:
            continue
        cost = match(*sig)
        if cost is not None:
            yield (op.end - op.start) * 1e-9, cost[0], cost[1]


def roofline_share(trace, kernel: str, device_kind: str) -> Optional[float]:
    """Percent of the roofline: the least time the chip could take for the
    kernel's calls (the larger of bytes over peak bandwidth and operations
    over peak rate) over the time they took. None where the trace holds no
    call of the kernel."""
    found = list(calls(trace, kernel))
    seconds = sum(c[0] for c in found)
    if not found or seconds <= 0:
        return None
    p = peaks(device_kind)
    least = max(sum(c[1] for c in found) / p["hbm_bytes_per_s"],
                sum(c[2] for c in found) / p["flops_per_s"])
    return 100.0 * least / seconds
