"""Reading a device event's name. On the chip the profiler names an event
by its HLO instruction's text:
    %closed_call.14 = bf16[8192,4096]{1,0:T(8,128)(2,1)} custom-call(
        bf16[8192,4096]{...} %bitcast.533, f32[4096]{...} %bitcast.584),
        custom_call_target="tpu_custom_call", ...
A Pallas kernel is a `tpu_custom_call`; its function's name is not in the
text, so a work file tells its kernel by the shapes in and out.
"""
from __future__ import annotations

import re
from typing import List, Optional, Tuple

Shape = Tuple[str, Tuple[int, ...], bool]    # dtype, dims, in fast memory
# a type with its layout, `bf16[8192,4096]{1,0:T(8,128)(2,1)S(1)}`: `S(1)`
# says that XLA keeps the array in on-chip memory, not in HBM
_TYPE = re.compile(r"\b(pred|bf16|f16|f32|f64|s8|s16|s32|s64|u8|u16|u32|u64)"
                   r"\[([\d,]*)\](\{[^}]*\})?")
DTYPE_BYTES = {"pred": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "s8": 1,
               "s16": 2, "s32": 4, "s64": 8, "u8": 1, "u16": 2, "u32": 4,
               "u64": 8}


def _shapes(text: str) -> List[Shape]:
    return [(d, tuple(int(x) for x in dims.split(",") if x),
             "S(1)" in layout) for d, dims, layout in _TYPE.findall(text)]


def pallas_call(name: str) -> Optional[Tuple[List[Shape], List[Shape]]]:
    """(outputs, operands) of a `tpu_custom_call` event, else None."""
    if 'custom_call_target="tpu_custom_call"' not in name:
        return None
    head, sep, rest = name.partition(" custom-call(")
    if not sep or " = " not in head:
        return None
    operands = rest.split("), custom_call_target=", 1)[0]
    return _shapes(head.split(" = ", 1)[1]), _shapes(operands)


def nbytes(shape: Shape) -> int:
    n = DTYPE_BYTES[shape[0]]
    for d in shape[1]:
        n *= d
    return n


def hbm_bytes(shapes: List[Shape]) -> float:
    """Bytes of the arrays that live in HBM: what the call has to move over
    the memory bus. An array XLA placed on chip costs the bus nothing, and
    counting it would put a kernel above its roofline."""
    return float(sum(nbytes(s) for s in shapes if not s[2]))
