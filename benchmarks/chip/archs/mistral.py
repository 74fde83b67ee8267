"""Mistral's decoder is the dense decoder of `qwen3.py` (no qk-norm, an
untied head; the configuration says which)."""
from archs.qwen3 import *  # noqa: F401,F403
