"""Model step, in the prompt cell: model FLOPs of the dispatches made while
the profiler ran over the traced window times the chip's bf16 peak
(`chipbench.work.mfu`). Moves ttft_p90_ms."""
from chipbench.work import mfu as read  # noqa: F401
