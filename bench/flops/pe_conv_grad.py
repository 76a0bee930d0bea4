"""Work of one call of the ``pe_conv_grad`` Pallas kernel, from its shapes.

On a TPU the trace names a device operation by its HLO instruction; the
kernel's is a custom call named after it,

    %pe_conv_grad.3 = f32[B,KH*KW,C,D] custom-call(<x>, <dy>), ...

whose operands are the capture x as (H, W, B, C) and the cotangent dy as
(Ho, Wo, B, D), each shape written beside the operand or in the call's
``operand_layout_constraints``.  Each example pairs each of its Ho·Wo
output positions with one input position per tap, so the kernel does
2·B·Ho·Wo·C·KH·KW·D FLOPs (a multiply-add counted as 2), with the taps it
is given: after space to depth that is the padded tap count (for AlexNet
conv0, 48 channels by 3x3 taps, 144 taps of the 11x11 kernel's 3).  Its
least HBM traffic is one read of each operand and one write of its f32
result.
"""
from __future__ import annotations

import dataclasses
import re

_HEAD = re.compile(r"^%?(pe_conv_grad[\w.\-]*) = (\w+)\[([\d,]+)\]")
_SHAPE = re.compile(r"\b(\w+)\[([\d,]+)\]")
_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4}


@dataclasses.dataclass(frozen=True)
class Call:
    x: tuple            # (H, W, B, C)
    dy: tuple           # (Ho, Wo, B, D)
    out: tuple          # (B, KH*KW, C, D)
    operand_bytes: int  # bytes per element of x and dy

    @property
    def flops(self) -> float:
        B, K, C, D = self.out
        Ho, Wo = self.dy[:2]
        return 2.0 * B * Ho * Wo * C * K * D

    @property
    def hbm_bytes(self) -> float:
        def n(shape):
            out = 1
            for d in shape:
                out *= d
            return out
        return (self.operand_bytes * (n(self.x) + n(self.dy))
                + 4 * n(self.out))


def _dims(text: str) -> tuple:
    return tuple(int(d) for d in text.split(",") if d)


def parse(text: str) -> Call | None:
    """The kernel call an operation's name describes, or ``None`` for
    another operation (or a name without the operands' shapes)."""
    head = _HEAD.match(text)
    if not head:
        return None
    out = _dims(head.group(3))
    rest = text[head.end():]
    at = rest.find("custom-call(")
    if at < 0 or len(out) != 4:
        return None
    rest = rest[at:].split("backend_config", 1)[0]
    shapes = [(t, _dims(d)) for t, d in _SHAPE.findall(rest)
              if len(_dims(d)) == 4]
    if len(shapes) < 2:
        return None
    (tx, x), (_, dy) = shapes[:2]
    if x[2] != out[0] or dy[2] != out[0] or x[3] != out[2] \
            or dy[3] != out[3]:
        return None
    return Call(x=x, dy=dy, out=out, operand_bytes=_BYTES.get(tx, 4))
