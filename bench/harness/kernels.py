"""The Mosaic (Pallas) kernels of a compiled step, and the least time the
chip could take for the work each does.

The compiled HLO names every kernel (``custom_call_target=
"tpu_custom_call"``) and gives its result and operand shapes; the trace
names its device op the same way.  Bytes are every operand read once and
every result written once.  FLOPs are those of the LSTM kernels' matmuls:

* forward (training, both directions in one kernel): each weight matrix
  (..., K, 4H) meets every output row once, ``2 R K 4H`` per matrix, R the
  rows of the first result (learners x time x batch);
* backward (one direction; results dx, dWx, dWh, db): four matmuls,
  twice the forward's count per weight matrix.
"""
from __future__ import annotations

import math
import re

_SHAPE = re.compile(r"\b(bf16|f16|f32|f64|s8|u8|s16|s32|u32|s64|pred|"
                    r"f8e4m3fn|f8e5m2)\[([\d,]*)\]")
_HEAD = re.compile(r"^\s*(?:ROOT\s+)?%(\S+)\s+=\s+(.*?)\s+custom-call\(")
_OPS = "operand_layout_constraints={"
ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "s8": 1, "u8": 1,
            "s16": 2, "s32": 4, "u32": 4, "s64": 8, "pred": 1,
            "f8e4m3fn": 1, "f8e5m2": 1}
TARGET = 'custom_call_target="tpu_custom_call"'


def _shapes(text: str) -> list:
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _SHAPE.findall(text)]


def _braced(line: str, start: int) -> str:
    """The text from ``start`` up to the brace that closes the one just
    before it."""
    depth = 1
    for i in range(start, len(line)):
        depth += {"{": 1, "}": -1}.get(line[i], 0)
        if depth == 0:
            return line[start:i]
    return line[start:]


def mosaic_calls(hlo_text: str) -> dict:
    """{instruction name: {"results": [...], "operands": [...]}} of the
    Mosaic kernels, each shape a (dtype, dims) pair."""
    out = {}
    for line in hlo_text.splitlines():
        if TARGET not in line:
            continue
        head = _HEAD.match(line)
        at = line.find(_OPS)
        if head is None or at < 0:
            continue
        out[head.group(1)] = {
            "results": _shapes(head.group(2)),
            "operands": _shapes(_braced(line, at + len(_OPS)))}
    return out


def nbytes(shapes) -> int:
    return sum(ITEMSIZE[dt] * math.prod(dims) for dt, dims in shapes)


def lstm_cost(call: dict):
    """(flops, bytes) of one LSTM kernel, or None when the shapes are not
    an LSTM forward or backward kernel's."""
    res, ops = call["results"], call["operands"]
    if not res:
        return None
    y = res[0][1]
    rows = math.prod(y[:-1])
    lead = len(y) - 3                   # learner dims before (T, B, .)
    backward = (len(res) == 4 and len(res[1][1]) >= 2
                and res[1][1][-1] == res[2][1][-1] == res[3][1][-1])
    gates = res[1][1][-1] if backward else 4 * y[-1]
    mats = [dims for dt, dims in ops
            if len(dims) == 2 + lead and dims[-1] == gates
            and dims[-2] != 1]
    if not mats:
        return None
    flops = (2 if backward else 1) * sum(2 * rows * d[-2] * gates
                                         for d in mats)
    return flops, nbytes(res) + nbytes(ops)


def min_time_s(flops: float, nbytes_: float, peak: dict):
    """(seconds, bound): the larger of compute and memory time."""
    t_c = flops / peak["bf16_flops"]
    t_m = nbytes_ / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
