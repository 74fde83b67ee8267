"""Faults planted in the timed path, to see the check come out false.

Each takes the built gateway after warm-up and breaks its engine's decode
step underneath the window, leaving everything else as it runs:

    state-unchanged   the decode step hands back the KV pool it was given,
                      so the new token's keys and values are never stored
    token-altered     every decoded token is replaced by its neighbour id
                      where the decode step produces it
"""
from __future__ import annotations


def state_unchanged(gw):
    eng = gw.replicas[0].engine
    step = eng._decode_tok

    def decode(params, toks, pos, cache, table):
        out, _ = step(params, toks, pos, cache, table)
        return out, cache
    eng._decode_tok = decode


def token_altered(gw):
    eng = gw.replicas[0].engine
    step = eng._decode_tok
    vocab = eng.cfg.vocab_size

    def decode(*args):
        out, cache = step(*args)
        return (out + 1) % vocab, cache
    eng._decode_tok = decode


FAULTS = {"state-unchanged": state_unchanged, "token-altered": token_altered}
