"""Operations and bytes that the algorithm needs, from shapes.

Counted as the work the computation requires, not as what today's code
happens to do: the paged-decode kernel is charged for the live K/V tokens
of each slot (and its q and output), not for the pages its grid walks, and
a prefill for its real tokens, not for the padding of its bucket. A kernel
or step that wastes work reads a lower share of its roofline; one that
stops wasting it reads higher, and no count can pass the chip's peak.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Optional, Sequence, Tuple

from .peaks import Peaks


@dataclass(frozen=True)
class Shape:
    """The sizes of a decoder that the counts need."""
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    itemsize: int = 2       # bytes per element of K/V, q and the output

    @classmethod
    def from_config(cls, c: dict) -> "Shape":
        return cls(d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
                   n_heads=c["num_attention_heads"],
                   n_kv_heads=c["num_key_value_heads"],
                   head_dim=c["head_dim"], d_ff=c["intermediate_size"],
                   vocab=c["vocab_size"],
                   itemsize=2 if c["torch_dtype"] == "bfloat16" else 4)

    @property
    def layer_matmul_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * (self.n_heads + 2 * self.n_kv_heads) * hd \
            + self.n_heads * hd * d
        return attn + 3 * d * self.d_ff


def paged_attention_call(shape: Shape,
                         live: Sequence[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode-attention call over one layer: each
    live slot's query attends its `live` keys (QK^T and PV, 2 FLOPs per
    multiply-add), reading each live K and V row once, its q once and
    writing its output once."""
    nh, nkv, hd, b = shape.n_heads, shape.n_kv_heads, shape.head_dim, \
        shape.itemsize
    tokens = sum(live)
    flops = 4.0 * nh * hd * tokens
    bytes_ = 2.0 * nkv * hd * b * tokens + 2.0 * nh * hd * b * len(live)
    return flops, bytes_


def least_time(flops: float, bytes_: float, peaks: Peaks) -> Tuple[float,
                                                                   str]:
    """The shortest time the chip could take, and which peak bounds it."""
    t_c, t_m = flops / peaks.bf16_flops, bytes_ / peaks.hbm_bytes
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def decode_token_flops(shape: Shape, live: int) -> float:
    """Model FLOPs of one decoded token that attends `live` keys: every
    matmul of every layer, attention, and one row of logits."""
    per_layer = 2.0 * shape.layer_matmul_params \
        + 4.0 * shape.n_heads * shape.head_dim * live
    return shape.n_layers * per_layer + 2.0 * shape.d_model * shape.vocab


def prefill_flops(shape: Shape, start: int, n_tok: int) -> float:
    """Model FLOPs of prefilling n_tok real tokens at positions start ..
    start + n_tok - 1 (causal: position p attends p + 1 keys), and the one
    row of logits the first generated token needs."""
    keys = n_tok * start + n_tok * (n_tok + 1) / 2.0
    per_layer = 2.0 * shape.layer_matmul_params * n_tok \
        + 4.0 * shape.n_heads * shape.head_dim * keys
    return shape.n_layers * per_layer + 2.0 * shape.d_model * shape.vocab


def model_flops(shape: Shape, decode_calls: Iterable[Sequence[int]],
                prefill_calls: Iterable[Tuple[int, int]]) -> float:
    """FLOPs of a set of engine dispatches: decode calls as lists of live
    key counts, prefill calls as (start, real tokens)."""
    total = 0.0
    for live in decode_calls:
        total += sum(decode_token_flops(shape, n) for n in live)
    for start, n_tok in prefill_calls:
        total += prefill_flops(shape, start, n_tok)
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
