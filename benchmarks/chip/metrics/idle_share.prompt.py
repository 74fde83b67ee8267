"""Device, in the prompt cell: share of the traced window in which no
operation ran (`chipbench.trace.idle_percent`). Moves ttft_p90_ms."""
from chipbench.trace import idle_percent as read  # noqa: F401
