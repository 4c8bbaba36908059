"""Operations and bytes of each Pallas kernel call in a compiled program,
from the operand and result shapes in its optimized HLO text, where a
kernel call is a ``tpu_custom_call`` instruction named after the kernel
(``%shuffle_gemm.11 = f32[16,1,4,159744]{...} custom-call(...),
custom_call_target="tpu_custom_call", operand_layout_constraints={...}``).

``shuffle_gemm`` (repro.kernels.shuffle_gemm) contracts a gathered
operand ``(b, G, t, C)`` with ``(G, n_out, t)`` into ``(b, G, n_out,
C)``: ``2 * b * G * n_out * C * t`` operations; its least traffic is its
operands and its result, read or written once.
"""

from __future__ import annotations

import re
from typing import Dict, List

ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "s8": 1, "u8": 1,
            "pred": 1}
_ARRAY = re.compile(r"\b(f32|bf16|f16|s32|s8|u8|pred)\[([0-9,]*)\]")


def _shapes(text: str):
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in _ARRAY.findall(text)]


def _nbytes(t, shape) -> int:
    n = ITEMSIZE[t]
    for d in shape:
        n *= d
    return n


def shuffle_gemm_cost(result, operands) -> Dict[str, int]:
    t = operands[0][1][2]
    n = 1
    for d in result[1]:
        n *= d
    return {"flops": 2 * n * t,
            "bytes": _nbytes(*result) + sum(_nbytes(*o) for o in operands)}


COST = {"shuffle_gemm": shuffle_gemm_cost}


def parse_calls(hlo_text: str, kernel: str) -> List[Dict[str, int]]:
    """One cost dict per ``tpu_custom_call`` instruction named after
    ``kernel`` in optimized HLO text: its result shape follows the
    ``=``, its operand shapes are the ``operand_layout_constraints``."""
    out = []
    for line in hlo_text.splitlines():
        line = line.strip()
        if not line.startswith(f"%{kernel}") \
                or 'custom_call_target="tpu_custom_call"' not in line:
            continue
        res = _shapes(line.split("=", 1)[1].split("custom-call(", 1)[0])
        ops = _shapes(line.split("operand_layout_constraints={", 1)[1]
                      .split("}, ", 1)[0] + "}")
        out.append(COST[kernel](res[0], ops))
    return out

