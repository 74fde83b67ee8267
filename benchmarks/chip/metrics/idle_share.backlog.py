"""Device, in the backlog cell: share of the traced window in which no
operation ran (`chipbench.trace.idle_percent`). Moves output_tok_s."""
from chipbench.trace import idle_percent as read  # noqa: F401
