"""Operations and bytes of the chunked delta rule's kernel (`megatron_tpu/
ops/kda_chunk.py::_kda_chunk`), counted from the shapes in the kernel's own
event text, and the least time the chip could take for them.

Which events: Pallas kernels (`custom-call`s with target `tpu_custom_call`)
whose instruction is named after the program's jitted function `_kda_chunk`,
or the `kCustom` fusion the compiler may make of the call and the write of
its state into the cache stacked over layers (`ssd_roofline.py`'s two
forms). The event's text carries the results' shapes and every operand's:

    %_kda_chunk.2 = (bf16[1,4096,4096]{...}, f32[1,32,128,128]{...})
        custom-call(bf16[1,4096,4096]{...} %q, bf16[1,4096,4096]{...} %k,
        bf16[1,4096,4096]{...} %v, f32[1,4096,4096]{...} %run,
        f32[1,16,4096,2]{...} %beta, f32[1,32,128,128]{...} %h0),
        custom_call_target="tpu_custom_call", ...

From them: sequences B and rows T (o [B, T, H d_v]); heads H, key channels
d_k and value channels d_v (the state [B, H, d_k, d_v]).

What is counted is THE RULE'S OWN work, whatever chunk a kernel takes, so
that a later kernel is read by the same yardstick. OPERATIONS, a row a head:
the decay of the state (d_k d_v), S'^T k (2 d_k d_v), the rank-one update
(d_k d_v) and S^T q (2 d_k d_v): 6 T H d_k d_v. The chunk form's own extras
(the triangular solve, the products between a chunk's rows, the
exponentials) are not counted, so the share reads low by nature and cannot
pass 100. BYTES: q, k, v in and o out at their item sizes; the log-decays
once a row a channel [B, T, H d_k] float32 and beta once a row a head
float32; the state in and out once a sequence, 2 x H d_k d_v x 4.

Per call the roofline time is the larger of operations / peak FLOP/s and
bytes / peak bytes/s (`peaks.json`): for a chunk of 4,096 rows of 32 heads
of 128, 12.9 GFLOP and 0.21 GB, so the bytes decide (0.25 ms). A trace's
share is the sum of those over the sum of the measured durations.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

# the floating-point arrays of an event's text, and their item sizes
from benchmark.moe_roofline import _ITEMSIZE, _shapes
from benchmark.program_spans import on_tpu
from benchmark.ssd_roofline import _braced
from benchmark.trace import is_pallas_kernel, parse_op

KERNEL = "_kda_chunk"


def is_kda_chunk(text: str) -> bool:
    """The kernel's own `custom-call`, or a `kCustom` fusion named after
    it."""
    name, opcode, _ = parse_op(text)
    return KERNEL in name and (
        is_pallas_kernel(text)
        or (opcode == "fusion" and "kind=kCustom" in text))


def counts(text: str) -> Optional[Tuple[float, float]]:
    """(the rule's operations, bytes) of one call, or None where the text
    does not hold the shapes of the chunked rule."""
    _, opcode, results = parse_op(text)
    operands = text.partition(f" {opcode}(")[2]
    ops = _shapes(operands.partition("custom_call_target")[0]
                  .partition("kind=")[0]) \
        or _shapes(_braced(operands.partition(
            "operand_layout_constraints=")[2]))  # operands by name alone
    out = _shapes(results)
    # the state [B, H, d_k, d_v] float32 among the results (fused with its
    # write, the stacked cache's [layers, B, H, d_k, d_v] is there instead)
    state = [s for t, s in out if t == "f32" and len(s) in (4, 5)]
    o = [(t, s) for t, s in out if len(s) == 3]
    if len(state) != 1 or len(o) != 1:
        return None
    heads, d_k, d_v = state[0][-3:]
    otype, (batch, rows, width) = o[0]
    if width != heads * d_v:
        return None
    # q, k and v (and, where d_k = d_v, no other): the rows' own dtype; the
    # running sums of the decays: the one float32 array of q's shape
    qkv = [t for t, s in ops
           if s in ((batch, rows, heads * d_k), (batch, rows, width))
           and t != "f32"]
    run = [s for t, s in ops
           if s == (batch, rows, heads * d_k) and t == "f32"]
    if otype == "f32":          # float32 rows: all four look alike
        qkv, run = ["f32"] * 3, run[:1]
    if len(qkv) != 3 or len(run) != 1:
        return None
    size = _ITEMSIZE
    flops = 6.0 * batch * rows * heads * d_k * d_v
    nbytes = (batch * rows * heads * (2 * d_k + d_v) * size[qkv[0]]
              + batch * rows * width * size[otype]
              + batch * rows * heads * d_k * 4 + batch * rows * heads * 4
              + 2 * batch * heads * d_k * d_v * 4)
    return flops, float(nbytes)


def roofline_seconds(text: str, peaks: dict) -> Optional[float]:
    c = counts(text)
    if c is None:
        return None
    return max(c[0] / peaks["bf16_flops_per_s"],
               c[1] / peaks["hbm_bytes_per_s"])


def kernel_events(trace) -> List[Tuple[str, float]]:
    """(text, duration in seconds) of every chunked-rule kernel on the first
    device; empty where the trace is not a TPU's or the program has no such
    kernel (a parent commit, a model without a KDA layer)."""
    if not on_tpu(trace):
        return []
    return [(name, d) for name, _, d in trace.ops[min(trace.ops)]
            if d > 0 and is_kda_chunk(name)]
