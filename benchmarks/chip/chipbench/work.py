"""Operations and bytes that the algorithm needs, from shapes.

Counted as the work the computation requires, not as what today's code
happens to do: the paged-decode kernel is charged for the live K/V tokens
of each slot (and its q and output), not for the pages its grid walks, and
a prefill for its real tokens, not for the padding of its bucket. A kernel
or step that wastes work reads a lower share of its roofline; one that
stops wasting it reads higher, and no count can pass the chip's peak.

The counts of one architecture are its module's (`archs/<model_type>.py`:
`work(c)`, which a run holds as `Run.shape`); the functions here take
that object.
"""
from __future__ import annotations

from typing import Iterable, Optional, Sequence, Tuple

from .peaks import Peaks


def least_time(flops: float, bytes_: float, peaks: Peaks) -> Tuple[float,
                                                                   str]:
    """The shortest time the chip could take, and which peak bounds it."""
    t_c, t_m = flops / peaks.bf16_flops, bytes_ / peaks.hbm_bytes
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def model_flops(shape, decode_calls: Iterable[Sequence[int]],
                prefill_calls: Iterable[Tuple[int, int]]) -> float:
    """FLOPs of a set of engine dispatches: decode calls as lists of live
    key counts, prefill calls as (start, real tokens)."""
    total = 0.0
    for live in decode_calls:
        total += sum(shape.decode_token_flops(n) for n in live)
    for start, n_tok in prefill_calls:
        total += shape.prefill_flops(start, n_tok)
    return total


def mfu(run) -> Optional[float]:
    """Model FLOPs of the prefill and decode dispatches made while the
    profiler ran, over the traced window times the chip's bf16 peak, in %;
    None where the run recorded no dispatch or knows no peak."""
    dec = [live for _, live in run.traced(run.decode_calls)]
    pre = [(start, n) for _, start, n in run.traced(run.prefill_calls)]
    if run.peaks is None or not (dec or pre):
        return None
    flops = model_flops(run.shape, dec, pre)
    return 100.0 * flops / (run.trace.window_s * run.peaks.bf16_flops)
