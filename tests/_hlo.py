"""The all-gathers of a compiled program, read from its HLO text: what each
gathers (the operand every device contributes) and the bytes that crosses
between devices, so a test can hold a mesh program's traffic to a hand
count instead of to the formula the program bills."""

from __future__ import annotations

import math
import re
from typing import Dict, List, NamedTuple, Tuple

_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
          "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}
# "%name = u32[4,8192,128]{2,1,0:T(8,128)} op(operands), attributes"
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\((.*)$")
# every collective that moves bytes between devices, sync or async
COLLECTIVE = re.compile(
    r"= .*\b(all-gather|all-reduce|reduce-scatter|collective-permute|"
    r"all-to-all)(-start)?\(")


class AllGather(NamedTuple):
    dtype: str
    operand: Tuple[int, ...]   # what each device contributes
    result: Tuple[int, ...]
    group: int                 # devices in each replica group

    @property
    def operand_bytes(self) -> int:
        return _BYTES[self.dtype] * math.prod(self.operand)


def squeezed(shape) -> Tuple[int, ...]:
    """``shape`` without its axes of one."""
    return tuple(int(d) for d in shape if d != 1)


def _dims(text: str) -> Tuple[int, ...]:
    return tuple(int(d) for d in text.split(",") if d)


def _group(attrs: str) -> int:
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", attrs)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"replica_groups=\[\d+,(\d+)\]", attrs)
    if m:
        return int(m.group(1))
    raise ValueError(f"no replica groups in {attrs[:200]!r}")


def all_gathers(hlo: str) -> List[AllGather]:
    """Every synchronous all-gather of the program.  A collective of any
    other kind, or an async one, is an error: its bytes would go uncounted."""
    shapes: Dict[str, Tuple[str, Tuple[int, ...]]] = {}
    gathers = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m:
            name, dtype, dims, op, rest = m.groups()
            shapes[name] = (dtype, _dims(dims))
            if op == "all-gather":
                gathers.append((dtype, _dims(dims), rest))
    n = sum(1 for line in hlo.splitlines() if COLLECTIVE.search(line))
    if n != len(gathers):
        raise ValueError(f"{n} collectives, of which {len(gathers)} are "
                         "synchronous all-gathers")
    out = []
    for dtype, result, rest in gathers:
        operand = re.match(r"\s*(?:\w+\[[\d,]*\]\S*\s+)?%([\w.\-]+)", rest)
        o_dtype, o_dims = shapes[operand.group(1)]
        assert o_dtype == dtype, (o_dtype, dtype)
        out.append(AllGather(dtype, o_dims, result, _group(rest)))
    return out


def received_bytes(hlo: str, devices: int) -> int:
    """Bytes the program's all-gathers bring to its ``devices`` devices
    together: each device receives the operand of every other device of
    its group."""
    return sum(devices * (g.group - 1) * g.operand_bytes
               for g in all_gathers(hlo))
