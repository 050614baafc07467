"""Operations and bytes of the grouped product of ONE CHIP'S SHARE of a
dropless expert layer (`megatron_tpu/models/moe.py`: a router over all the
layer's experts, banks of the experts held here), and the least time the chip
could take for them.

The events are `benchmark/moe_roofline.py`'s (`_moe_grouped_matmul*` Pallas
kernels, shapes from the event's own text): rows [m, k] x bank [G, k, n] ->
[m, n]. What differs is what may be credited. The call is GIVEN m rows, every
(token, choice) of the program's tokens, but only the rows whose expert is
held here are multiplied: the others lie behind the last group and the kernel
skips them. `moe_roofline.counts` would credit 2 m k n; this credits

    operations = 2 m_held k n
    bytes      = m_held (k + n) x the rows' itemsize
                 + banks touched x k x n x the bank's itemsize

with m_held never above m and banks touched never above the experts held nor
above m_held. m_held and the banks touched are the router's choice and are not
in the trace; the driver measures both on the window's own tokens with the
reference's float32 router (`drivers/serve_open_loop_command_a.py`:
`held_row_share`, and for a call of a decode step's size
`held_rows_per_decode_step` and `groups_hit_per_decode_step`), and the reader
hands them in. A skipped row is never credited.
"""
from __future__ import annotations

from typing import Optional, Tuple

from benchmark.moe_roofline import _ITEMSIZE, _shapes, rows_of


def counts(text: str, held_share: float, experts_held: int,
           held_rows: Optional[float] = None,
           banks: Optional[float] = None) -> Optional[Tuple[float, float]]:
    """(operations, bytes) of one call. `held_share`: of the m rows given,
    the share whose expert is held (0..1); `held_rows` overrides m x
    held_share where the driver counted the rows themselves (a decode
    step); `banks`: the held experts those rows touch, where counted. None
    where the text is not rows x bank."""
    head, _, operands = text.partition("custom-call(")
    arrays = _shapes(operands.partition("custom_call_target")[0])
    out, m = _shapes(head), rows_of(text)
    if m is None or not experts_held:
        return None
    otype, (_, n) = out[0]
    bank = [(t, s) for t, s in arrays if len(s) == 3 and s[2] == n]
    if not bank:
        return None
    btype, (_, k, _) = bank[0]
    rows = [t for t, s in arrays if s == (m, k)]
    if not rows:
        return None
    m_held = min(float(m), max(
        0.0, held_rows if held_rows is not None else m * held_share))
    touched = min(float(experts_held), m_held,
                  banks if banks is not None else float(experts_held))
    return (2.0 * m_held * k * n,
            m_held * (k * _ITEMSIZE[rows[0]] + n * _ITEMSIZE[otype])
            + touched * k * n * _ITEMSIZE[btype])


def least_seconds(text: str, peaks: dict, held_share: float,
                  experts_held: int, held_rows: Optional[float] = None,
                  banks: Optional[float] = None) -> Optional[float]:
    c = counts(text, held_share, experts_held, held_rows, banks)
    if c is None:
        return None
    return max(c[0] / peaks["bf16_flops_per_s"],
               c[1] / peaks["hbm_bytes_per_s"])
