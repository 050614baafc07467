"""Operations and bytes of the grouped product of a dropless expert layer
(`megatron_tpu/ops/grouped_matmul.py`), counted from the shapes in the
kernel's own event text, and the least time the chip could take for them.

Which events: Pallas kernels (`custom-call`s with target `tpu_custom_call`)
whose instruction is named after the program's jitted functions
`_moe_grouped_matmul` (rows [m, k] x bank [E, k, n] -> [m, n]),
`_moe_grouped_matmul_dlhs` (the same product against the bank transposed,
the backward pass's gradient of the rows) and `_moe_grouped_matmul_drhs`
([k, m] x [m, n] -> [E, k, n], the gradient of the bank). The event's text
carries the result's shape and every operand's:

    %_moe_grouped_matmul.3 = bf16[256,2048]{...} custom-call(s32[65]{...} %a,
        ..., bf16[256,2048]{...} %rows, bf16[64,2048,2048]{...} %bank),
        custom_call_target="tpu_custom_call", ...

Only what cannot be avoided is counted. Operations: 2 m k n for the m rows
given (the grid's idle slots and a bucket's padding are rows like any other:
the program multiplies them). Bytes: the rows in, the rows out, and one
k x n matrix for each expert the rows touch: `min(E, m)` of them, every
expert that could have a row, unless the caller knows better. How many they
touch is the router's choice and is not in the trace; the driver measures it
on the window's own traffic (`drivers/serve_open_loop_olmoe.py`:
`groups_hit_per_decode_step`, the mean number of experts with a row when a
decode grid's worth of the window's tokens is routed by the reference's
router) and the reader hands that in as `banks` for the calls of a decode
step's size. Those are the calls it matters for: they are bound by the
bank's bytes, where a prefill's thousands of rows are bound by the products
and touch every expert. In the cell as it stands (the embedding drawn at unit
scale, 24 slots) the measured count is 61.3 to 61.6 of 64 (my chip runs, PR 27;
PERF.md section 6); under the drawn embedding the cell first had, one
request's tokens shared their 8 experts, and `min(E, m)` read 93.6 % for a
kernel that streamed perhaps half the banks it was credited with. The grid's
idle slots
are routed too and the draw holds live tokens only, so the count is high by
what the idle slots share, a bank or two. The gradient of the bank writes all
E matrices, hit or not.

Per call the roofline time is the larger of operations / peak FLOP/s and
bytes / peak bytes/s (`peaks.json`); a trace's share is the sum of those over
the sum of the measured durations.
"""
from __future__ import annotations

import re
from typing import List, Optional, Tuple

from benchmark.program_spans import on_tpu
from benchmark.trace import is_pallas_kernel, parse_op

KERNEL = "_moe_grouped_matmul"
# the floating-point arrays of an event's text; the kernel's integer
# operands (group metadata) are not rows or banks
_SHAPE = re.compile(r"\b(bf16|f16|f32)\[([\d,]*)\]")
_ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4}


def is_grouped_matmul(text: str) -> bool:
    return is_pallas_kernel(text) and KERNEL in parse_op(text)[0]


def _shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in _SHAPE.findall(text)]


def rows_of(text: str) -> Optional[int]:
    """m of a rows x bank call; None for the bank's gradient and for a text
    without a result's shape."""
    out = _shapes(text.partition("custom-call(")[0])
    return out[0][1][0] if len(out) == 1 and len(out[0][1]) == 2 else None


def counts(text: str, banks: Optional[float] = None
           ) -> Optional[Tuple[float, float]]:
    """(operations, bytes) of one call, or None where the text does not hold
    the shapes of a grouped product. `banks` is how many of the E matrices
    the call's rows touch, where that was measured; `min(E, m)` otherwise,
    and never more than that."""
    head, _, operands = text.partition("custom-call(")
    out = _shapes(head)
    ops = _shapes(operands.partition("custom_call_target")[0])
    if len(out) != 1:
        return None
    (otype, oshape), size = out[0], _ITEMSIZE
    if len(oshape) == 2:                     # rows x bank
        m, n = oshape
        bank = [s for s in ops if len(s[1]) == 3 and n in s[1][1:]]
        rows = [s for s in ops if len(s[1]) == 2 and s[1][0] == m]
        if not bank or not rows:
            return None
        (btype, (e, a, b)), (rtype, (_, k)) = bank[0], rows[0]
        if {a, b} != {k, n}:
            return None
        return (2.0 * m * k * n,
                float(m * k * size[rtype] + m * n * size[otype]
                      + min(e, m, banks or e) * k * n * size[btype]))
    if len(oshape) == 3:                     # the bank's gradient
        e, k, n = oshape
        lhs = [s for s in ops if len(s[1]) == 2 and s[1][0] == k]
        grad = [s for s in ops if len(s[1]) == 2 and s[1][1] == n]
        if not lhs or not grad or lhs[0][1][1] != grad[0][1][0]:
            return None
        m = lhs[0][1][1]
        return (2.0 * m * k * n,
                float(m * k * size[lhs[0][0]] + m * n * size[grad[0][0]]
                      + e * k * n * size[otype]))
    return None


def roofline_seconds(text: str, peaks: dict, banks: Optional[float] = None
                     ) -> Optional[float]:
    c = counts(text, banks)
    if c is None:
        return None
    return max(c[0] / peaks["bf16_flops_per_s"], c[1] / peaks["hbm_bytes_per_s"])


def kernel_events(trace) -> List[Tuple[str, float]]:
    """(text, duration in seconds) of every grouped-product kernel on the
    first device; empty where the trace is not a TPU's or the program has no
    such kernel (a parent commit, a model without experts)."""
    if not on_tpu(trace):
        return []
    return [(name, d) for name, _, d in trace.ops[min(trace.ops)]
            if d > 0 and is_grouped_matmul(name)]
