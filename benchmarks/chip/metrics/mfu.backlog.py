"""Model step, in the backlog cell: model FLOPs of the dispatches made while
the profiler ran over the traced window times the chip's bf16 peak
(`chipbench.work.mfu`). Moves output_tok_s."""
from chipbench.work import mfu as read  # noqa: F401
