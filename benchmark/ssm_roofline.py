"""Bytes of the selective scan's kernel (`megatron_tpu/ops/
selective_scan.py::_ssm_selective_scan`), counted from the shapes in the
kernel's own event text, and the least time the chip could take for them.

Which events: Pallas kernels (`custom-call`s with target `tpu_custom_call`)
whose instruction is named after the program's jitted function
`_ssm_selective_scan`. The event's text carries the results' shapes and
every operand's:

    %_ssm_selective_scan.3 = (bf16[1,2048,5120]{...}, f32[1,16,5120]{...})
        custom-call(bf16[1,2048,5120]{...} %x, f32[1,2048,5120]{...} %dt,
        bf16[1,2048,5120]{...} %z, f32[1,2048,16,128]{...} %b,
        f32[1,2048,16,128]{...} %c, f32[16,5120]{...} %a, f32[1,5120]{...} %d,
        f32[1,16,5120]{...} %h0), custom_call_target="tpu_custom_call", ...

In a served chunk the compiler fuses the call with the write of its state
into the cache stacked over layers, and the event is that fusion, named
after the kernel all the same (my chip run, PR 47):

    %_ssm_selective_scan.13 = (f32[26,1,16,5120]{...}, bf16[1,2048,5120]{...})
        fusion(f32[26,1,16,5120]{...} %cache, s32[] %layer, <the call's eight
        operands>), kind=kCustom, calls=%fused_computation...

Only what cannot be avoided is counted. OPERATIONS ON THE MATRIX UNIT: 0.
The recurrence is elementwise over [d_state, d_inner] a row (an exponential,
three products and two sums a state value a row, on the vector and
exponential units, which `peaks.json` has no peak for), so the bytes decide
the least time and the share reads low for a kernel those units bound: it
says how far the kernel is from streaming its rows, never more than 100.
BYTES: the rows' arrays [sequences, rows, d_inner] at their own item sizes
(x, dt and z in, y out); B and C once a row, [sequences, rows, d_state]
values of their item size (the kernel is handed them spread along 128 lanes,
which is the wrapper's doing and 8 times what the algorithm needs: not
counted); the state in and out once a sequence, 2 x d_state x d_inner x 4;
A and D once.

Per call the roofline time is bytes / peak bytes/s (`peaks.json`); a
trace's share is the sum of those over the sum of the measured durations.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

# the floating-point arrays of an event's text, and their item sizes
from benchmark.moe_roofline import _ITEMSIZE, _shapes
from benchmark.program_spans import on_tpu
from benchmark.trace import is_pallas_kernel, parse_op

KERNEL = "_ssm_selective_scan"


def is_selective_scan(text: str) -> bool:
    """The kernel's own `custom-call`, or the `kCustom` fusion the compiler
    makes of it and the write of its state into the stacked cache (named
    after the kernel too: a served chunk's form)."""
    name, opcode, _ = parse_op(text)
    return KERNEL in name and (
        is_pallas_kernel(text)
        or (opcode == "fusion" and "kind=kCustom" in text))


def counts(text: str) -> Optional[Tuple[float, float]]:
    """(operations on the matrix unit, bytes) of one call, or None where
    the text does not hold the shapes of a scan."""
    _, opcode, results = parse_op(text)
    operands = text.partition(f" {opcode}(")[2]
    ops = _shapes(operands.partition("custom_call_target")[0]
                  .partition("kind=")[0]) \
        or _shapes(operands.partition("operand_layout_constraints={")[2]
                   .partition("}, ")[0])        # operands by name alone
    # B and C as the kernel is handed them: [sequences, rows, d_state, 128]
    spread = [s for s in ops if len(s[1]) == 4 and s[1][3] == 128]
    if len(spread) != 2 or spread[0][1] != spread[1][1]:
        return None
    batch, rows, d_state, _ = spread[0][1]
    # y [sequences, rows, d_inner] among the results, x, dt and z like it
    # among the operands
    y = [s for s in _shapes(results)
         if len(s[1]) == 3 and s[1][:2] == (batch, rows)]
    if len(y) != 1:
        return None
    (ytype, yshape), size = y[0], _ITEMSIZE
    rows_in = [s for s in ops if s[1] == yshape]
    if len(rows_in) != 3:
        return None
    n_rows, d_inner = batch * rows, yshape[2]
    nbytes = (n_rows * d_inner * (sum(size[t] for t, _ in rows_in)
                                  + size[ytype])
              + sum(n_rows * d_state * size[t] for t, _ in spread)
              + 2 * batch * d_state * d_inner * 4
              + (d_state + 1) * d_inner * 4)
    return 0.0, float(nbytes)


def roofline_seconds(text: str, peaks: dict) -> Optional[float]:
    c = counts(text)
    if c is None:
        return None
    return max(c[0] / peaks["bf16_flops_per_s"],
               c[1] / peaks["hbm_bytes_per_s"])


def kernel_events(trace) -> List[Tuple[str, float]]:
    """(text, duration in seconds) of every scan kernel on the first
    device; empty where the trace is not a TPU's or the program has no such
    kernel (a parent commit, a model without a scan)."""
    if not on_tpu(trace):
        return []
    return [(name, d) for name, _, d in trace.ops[min(trace.ops)]
            if d > 0 and is_selective_scan(name)]
