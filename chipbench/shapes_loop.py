"""Bytes of a looped (weight-tied) stack's decode step, computed from
shapes, for its roofline share. Kept with the benchmark so that no PR
that claims a gain can change them. ``shapes.py`` is the stack run once.

Keys are those of the configuration file (the published config.json):
``num_hidden_layers`` layers of weights are run ``total_ut_steps`` times a
token, and every (pass, layer) pair keeps keys and values of its own.
"""

from __future__ import annotations

from chipbench import shapes

BF16 = 2


def passes(hf: dict) -> int:
    return int(hf["total_ut_steps"])


def decode_weight_bytes(hf: dict) -> int:
    """Weight bytes one decode step must read: every layer's matrices once
    a pass (the chip holds ~100 MB of fast memory and a pass reads 4.9 GB,
    so nothing read in one pass is still near in the next) and the output
    head once. The embedding is a gather of a few rows; norms are
    negligible and left out (the floor is a lower bound)."""
    head = hf["hidden_size"] * hf["vocab_size"]
    layers = hf["num_hidden_layers"] * shapes.layer_params(hf)
    return BF16 * (passes(hf) * layers + head)


def kv_bytes_per_token(hf: dict) -> int:
    """Keys and values one token of context holds: a cache layer for
    every (pass, layer) pair."""
    return passes(hf) * shapes.kv_bytes_per_token(hf)


def decode_step_floor_s(hf: dict, live_kv_tokens: float, chips: int,
                        hbm_bytes_per_s: float) -> float:
    """Least time of one decode step on each of ``chips`` chips that share
    weights and KV by head: bytes per chip over one chip's HBM peak."""
    total = decode_weight_bytes(hf) + live_kv_tokens * kv_bytes_per_token(hf)
    return total / chips / hbm_bytes_per_s
