"""Operations and bytes of one call of each Pallas kernel, from its
shapes as the compiled HLO states them (the custom call's operand and
result types, batch dimension from vmap included).

Bytes are what the call must move at the least: every operand read once
and every result written once, in their stored types. Operations count
the floating-point multiply-adds the algorithm needs, two per term:

  chop      elementwise rounding: no floating-point operations, so its
            roofline is its bytes over the memory bandwidth
  qmv       y = A v on A (B, M, K): 2 B M K
  qmatmul   C = A B on A (B, M, K), B (B, K, N): 2 B M K N
  trisolve  one triangular substitution on an n x n factor, given as
            (B, nb, n, block) column blocks with n = nb block: B n^2
"""
import math
import re

ITEMSIZE = {"f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8, "bf16": 2,
            "f16": 2, "s8": 1, "u8": 1, "pred": 1}
_TYPE = re.compile(r"\b(f32|s32|u32|f64|s64|bf16|f16|s8|u8|pred)"
                   r"\[([0-9,]*)\]")


def parse_types(text: str):
    """[(dtype, shape)] of every array type written in `text`."""
    return [(d, tuple(int(x) for x in dims.split(",") if x))
            for d, dims in _TYPE.findall(text)]


def _braced(text: str, key: str) -> str:
    """The text inside `key{...}`, nested braces included."""
    i = text.find(key + "{")
    if i < 0:
        return ""
    depth, j = 0, i + len(key)
    for k in range(j, len(text)):
        depth += {"{": 1, "}": -1}.get(text[k], 0)
        if depth == 0:
            return text[j + 1:k]
    return ""


def parse_custom_call(line: str):
    """(instruction, kernel, results, operands) of one HLO custom-call
    line: the kernel named by the `/<kernel>/pallas_call` of its
    op_name, the operand types from `operand_layout_constraints` (a
    scheduled module names its operands without their types)."""
    inst = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", line)
    m = re.search(r"/(\w+)/pallas_call", line)
    head, _, rest = line.partition("custom-call(")
    res = head.split("=", 1)[1] if "=" in head else head
    ops = _braced(line, "operand_layout_constraints=")
    if not ops:
        ops = rest.split(")", 1)[0]
    return (inst.group(1) if inst else None, m.group(1) if m else None,
            parse_types(res), parse_types(ops))


def nbytes(types) -> int:
    return sum(ITEMSIZE[d] * math.prod(s) for d, s in types)


def _largest(types):
    return max((s for d, s in types if d == "f32"),
               key=lambda s: math.prod(s), default=())


def cost(kernel: str, results, operands):
    """(flops, bytes) of one call."""
    moved = nbytes(results) + nbytes(operands)
    f32 = [s for d, s in operands if d == "f32"]
    if kernel == "chop":
        return 0.0, float(moved)
    if kernel == "qmv":
        a = _largest(operands)
        return 2.0 * math.prod(a), float(moved)
    if kernel == "qmatmul":
        a, b = f32[0], f32[1]
        return 2.0 * math.prod(a) * b[-1], float(moved)
    if kernel == "trisolve":
        lu = _largest(operands)            # (..., nb, n, block)
        n = lu[-2]
        batch = math.prod(lu[:-3])
        return float(batch * n * n), float(moved)
    raise KeyError(f"no cost model for kernel {kernel!r}")
