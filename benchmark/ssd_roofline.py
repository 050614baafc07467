"""Operations and bytes of the chunked scan's kernel (`megatron_tpu/ops/
ssd_scan.py::_ssd_chunk_scan`), counted from the shapes in the kernel's own
event text, and the least time the chip could take for them.

Which events: Pallas kernels (`custom-call`s with target `tpu_custom_call`)
whose instruction is named after the program's jitted function
`_ssd_chunk_scan`, or the `kCustom` fusion the compiler may make of the call
and the write of its state into the cache stacked over layers
(`ssm_roofline.py` saw that form of Jamba's scan). The event's text carries
the results' shapes and every operand's:

    %_ssd_chunk_scan.2 = (bf16[1,2048,8192]{...}, f32[1,128,64,128]{...})
        custom-call(bf16[1,2048,8192]{...} %x, bf16[1,2048,1024]{...} %b,
        bf16[1,2048,1024]{...} %c, f32[1,8,2048,16]{...} %run_col,
        f32[1,8,2048,16]{...} %w_col, f32[1,128,2048]{...} %run_row,
        f32[1,128,2048]{...} %dt_row, f32[1,16,128,128]{...} %end,
        f32[1,8192]{...} %d, f32[1,128,64,128]{...} %h0),
        custom_call_target="tpu_custom_call", ...

From them: sequences B and rows R (y [B, R, H P]); heads H, channels a head
P and state N (the state [B, H, P, N]); groups G (b [B, R, G N]); chunks n
and so the chunk's rows Q = R / n (the chunks' whole decays, [B, n, H, N]).

Only what the chunked algorithm cannot avoid is counted. OPERATIONS: four
products a chunk: C B^T once a GROUP (2 Q Q N), and a head the mix times x
(2 Q Q P), C times the state (2 Q P N) and the weighted rows' outer product
into the state (2 Q P N): B n (G 2 Q Q N + H (2 Q Q P + 4 Q P N)). The
decays' exponentials (Q Q a head a chunk) run on the vector and exponential
units, which `peaks.json` has no peak for: not counted, so the share reads
low where they bound the kernel. BYTES: x in and y out [B, R, H P] at their
item sizes; B and C once a row [B, R, G N]; the step sizes once, [B, R, H]
float32 (the kernel is handed their running sums in two orientations beside
them, the wrapper's doing: not counted); the state in and out once a
sequence, 2 x H P N x 4; D once.

Per call the roofline time is the larger of operations / peak FLOP/s and
bytes / peak bytes/s (`peaks.json`); a trace's share is the sum of those
over the sum of the measured durations.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

# the floating-point arrays of an event's text, and their item sizes
from benchmark.moe_roofline import _ITEMSIZE, _shapes
from benchmark.program_spans import on_tpu
from benchmark.trace import is_pallas_kernel, parse_op

KERNEL = "_ssd_chunk_scan"


def is_chunk_scan(text: str) -> bool:
    """The kernel's own `custom-call`, or a `kCustom` fusion named after
    it."""
    name, opcode, _ = parse_op(text)
    return KERNEL in name and (
        is_pallas_kernel(text)
        or (opcode == "fusion" and "kind=kCustom" in text))


def _braced(text: str) -> str:
    """What the braces that open `text` hold, inner braces and all."""
    depth = 0
    for i, c in enumerate(text):
        depth += (c == "{") - (c == "}")
        if depth == 0:
            return text[1:i]
    return ""


def counts(text: str) -> Optional[Tuple[float, float]]:
    """(operations on the matrix unit, bytes) of one call, or None where
    the text does not hold the shapes of a chunked scan."""
    _, opcode, results = parse_op(text)
    operands = text.partition(f" {opcode}(")[2]
    ops = _shapes(operands.partition("custom_call_target")[0]
                  .partition("kind=")[0]) \
        or _shapes(_braced(operands.partition(
            "operand_layout_constraints=")[2]))  # operands by name alone
    out = _shapes(results)
    # the state [B, H, P, N] float32 among the results (fused with its
    # write, the stacked cache's [layers, B, H, P, N] is there instead)
    state = [s for t, s in out if t == "f32" and len(s) in (4, 5)]
    y = [(t, s) for t, s in out if len(s) == 3]
    if len(state) != 1 or len(y) != 1:
        return None
    heads, head_dim, d_state = state[0][-3:]
    ytype, (batch, rows, width) = y[0]
    if width != heads * head_dim:
        return None
    x = [t for t, s in ops if s == (batch, rows, width)]
    bc = [(t, s) for t, s in ops
          if len(s) == 3 and s[:2] == (batch, rows) and s[2] != width
          and s[2] % d_state == 0]
    end = [s for t, s in ops
           if len(s) == 4 and s[0] == batch and s[2:] == (heads, d_state)]
    if len(x) != 1 or len(bc) != 2 or len(end) != 1 \
            or bc[0][1] != bc[1][1] or rows % end[0][1]:
        return None
    groups, chunks = bc[0][1][2] // d_state, end[0][1]
    q = rows // chunks
    flops = batch * chunks * (
        groups * 2.0 * q * q * d_state
        + heads * (2.0 * q * q * head_dim + 4.0 * q * head_dim * d_state))
    size = _ITEMSIZE
    nbytes = (batch * rows * width * (size[x[0]] + size[ytype])
              + sum(batch * rows * groups * d_state * size[t] for t, _ in bc)
              + batch * rows * heads * 4
              + 2 * batch * heads * head_dim * d_state * 4
              + heads * 4)
    return flops, float(nbytes)


def roofline_seconds(text: str, peaks: dict) -> Optional[float]:
    c = counts(text)
    if c is None:
        return None
    return max(c[0] / peaks["bf16_flops_per_s"],
               c[1] / peaks["hbm_bytes_per_s"])


def kernel_events(trace) -> List[Tuple[str, float]]:
    """(text, duration in seconds) of every chunked-scan kernel on the first
    device; empty where the trace is not a TPU's or the program has no such
    kernel (a parent commit, a model without a Mamba-2 layer)."""
    if not on_tpu(trace):
        return []
    return [(name, d) for name, _, d in trace.ops[min(trace.ops)]
            if d > 0 and is_chunk_scan(name)]
