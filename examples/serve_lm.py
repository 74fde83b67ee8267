"""Batched serving demo: the queue-backed gateway streaming tokens from a
reduced assigned arch with per-request sampling.

    PYTHONPATH=src python examples/serve_lm.py [--arch mamba2-130m] \
        [--policy least-loaded] [--temperature 0.8] [--stream]

Every prompt is published to the durable TaskQueue, dispatched to an engine
replica by the chosen policy, and decoded with its own SamplingParams; with
--stream the tokens print as each lockstep decode step lands (the
`on_token` callback fires inside `Gateway.step`, not after `run()`).
"""
import argparse
import sys

from repro.launch import serve


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--policy", default="round-robin")
    ap.add_argument("--temperature", type=float, default=0.7)
    ap.add_argument("--top-k", type=int, default=8)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kv-layout", default="dense",
                    choices=("dense", "paged"),
                    help="'paged' turns on the block-pool KV cache with "
                    "radix-tree prefix reuse (pure-attention archs); pair "
                    "with --policy prefix-affinity to see routing follow "
                    "the cache")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--admit-budget", type=int, default=None,
                    help="token-budget admission control (429 rejects)")
    ap.add_argument("--stream", action="store_true", default=True,
                    help="print tokens as they decode (default on)")
    ap.add_argument("--no-stream", dest="stream", action="store_false")
    ap.add_argument("--dashboard", action="store_true", default=True,
                    help="print the queue/slot dashboard (default on)")
    ap.add_argument("--no-dashboard", dest="dashboard",
                    action="store_false")
    args = ap.parse_args()
    argv = [sys.argv[0], "--arch", args.arch, "--reduced",
            "--requests", str(args.requests),
            "--replicas", str(args.replicas),
            "--policy", args.policy,
            "--temperature", str(args.temperature),
            "--top-k", str(args.top_k),
            "--top-p", str(args.top_p),
            "--seed", str(args.seed),
            "--kv-layout", args.kv_layout,
            "--block-size", str(args.block_size)]
    if args.admit_budget is not None:
        argv += ["--admit-budget", str(args.admit_budget)]
    if args.dashboard:
        argv.append("--dashboard")
    if args.stream:
        argv.append("--stream")
    sys.argv = argv
    serve.main()


if __name__ == "__main__":
    main()
