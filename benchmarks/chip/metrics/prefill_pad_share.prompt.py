"""Model step: share of the positions the prefill program computes that
are padding, Σ(padded − tokens) ÷ Σ padded over the prefill `engine.step`
spans that start in the window (the program pads each prompt's uncached
tokens to its prefill bucket)."""
from chipbench import listen


def read(run):
    steps = [args for s, _, args in listen.spans("engine.step", step="prefill")
             if run.t0 <= s <= run.t1]
    padded = sum(a["padded"] for a in steps)
    if not padded:
        return None
    return 100.0 * (padded - sum(a["tokens"] for a in steps)) / padded
