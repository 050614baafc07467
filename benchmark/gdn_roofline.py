"""Operations and bytes of the scalar-decay chunked delta rule's kernel
(`megatron_tpu/ops/kda_chunk.py::_gdn_chunk`), counted from the shapes in the
kernel's own event text, and the least time the chip could take for them.
`kda_roofline.py`'s yardstick for a rule whose decay is ONE number a head a
row and whose value heads read fewer key heads.

Which events: Pallas kernels (`custom-call`s with target `tpu_custom_call`)
whose instruction is named after the program's jitted function `_gdn_chunk`,
or the `kCustom` fusion the compiler may make of the call and the write of
its state into the cache stacked over layers. The event's text carries the
results' shapes and every operand's:

    %_gdn_chunk.2 = (bf16[1,4096,4096]{...}, f32[1,32,128,128]{...})
        custom-call(bf16[1,4096,2048]{...} %q, bf16[1,4096,2048]{...} %k,
        bf16[1,4096,4096]{...} %v, f32[1,16,4096,2]{...} %run,
        f32[1,16,4096,2]{...} %beta, f32[1,32,128,128]{...} %h0),
        custom_call_target="tpu_custom_call", ...

From them: sequences B and rows T (o [B, T, H d_v]); value heads H, key
channels d_k and value channels d_v (the state [B, H, d_k, d_v]); the key
heads' width H_k d_k (q's and k's last axis).

What is counted is THE RULE'S OWN work, as `kda_roofline.py` counts it, so
that a later kernel is read by the same yardstick. OPERATIONS, a row a value
head: the decay of the state (d_k d_v), S'^T k (2 d_k d_v), the rank-one
update (d_k d_v) and S^T q (2 d_k d_v): 6 T H d_k d_v. The chunk form's own
extras (the triangular solve, K K^T and Q K^T, the exponentials) are not
counted, so the share reads low by nature and cannot pass 100. BYTES: q and
k of the KEY heads once, v in and o out, at their item sizes; the log-decays
and beta once a row a HEAD, float32 [B, T, H] each; the state in and out
once a sequence, 2 x H d_k d_v x 4.

Per call the roofline time is the larger of operations / peak FLOP/s and
bytes / peak bytes/s (`peaks.json`): for a chunk of 4,096 rows of 16 key
heads under 32 value heads of 128, 12.9 GFLOP and 0.106 GB, so the bytes
decide (0.13 ms). A trace's share is the sum of those over the sum of the
measured durations.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from benchmark.moe_roofline import _ITEMSIZE, _shapes
from benchmark.program_spans import on_tpu
from benchmark.ssd_roofline import _braced
from benchmark.trace import is_pallas_kernel, parse_op

KERNEL = "_gdn_chunk"


def is_gdn_chunk(text: str) -> bool:
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
    # q, k (the key heads' width) and v (the value heads'): the three arrays
    # of [B, T, .]; the running sums and beta a column a head: the two of
    # [B, blocks of heads, T, heads a block]
    by_rows = [(t, s) for t, s in ops
               if len(s) == 3 and s[:2] == (batch, rows)]
    by_head = [s for t, s in ops
               if t == "f32" and len(s) == 4 and s[0] == batch
               and s[2] == rows and s[1] * s[3] == heads]
    if len(by_rows) != 3 or len(by_head) != 2:
        return None
    if any(s[2] % d_k and s[2] != width for _, s in by_rows):
        return None
    size = _ITEMSIZE
    flops = 6.0 * batch * rows * heads * d_k * d_v
    nbytes = (sum(batch * rows * s[2] * size[t] for t, s in by_rows)
              + batch * rows * width * size[otype]
              + 2 * batch * rows * heads * 4
              + 2 * batch * heads * d_k * d_v * 4)
    return flops, float(nbytes)


def roofline_seconds(text: str, peaks: dict) -> Optional[float]:
    c = counts(text)
    if c is None:
        return None
    return max(c[0] / peaks["bf16_flops_per_s"],
               c[1] / peaks["hbm_bytes_per_s"])


def kernel_events(trace) -> List[Tuple[str, float]]:
    """(text, duration in seconds) of every scalar-decay chunk kernel on the
    first device; empty where the trace is not a TPU's or the program has no
    such kernel (a parent commit, a model without such a layer)."""
    if not on_tpu(trace):
        return []
    return [(name, d) for name, _, d in trace.ops[min(trace.ops)]
            if d > 0 and is_gdn_chunk(name)]
