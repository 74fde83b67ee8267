"""Engine step, in the backlog cell: share of the traced window the worker
spent in the engine's host work, `worker.step` less its `engine.wait`
(`chipbench.listen.engine_host_share`). Moves output_tok_s."""
from chipbench.listen import engine_host_share as read  # noqa: F401
