"""Reads a configuration file: the published keys at the top level, as the
model's own ``config.json`` names them, plus ``assumed`` (sizes the source
does not give) and ``served`` (how the weights are stored and served)."""

from __future__ import annotations

import json


def load_config(path: str) -> dict:
    with open(path) as f:
        cfg = json.load(f)
    assumed = cfg.get("assumed", {})
    cfg["head_dim"] = assumed.get(
        "head_dim", cfg["hidden_size"] // cfg["num_attention_heads"])
    cfg["qkv_bias"] = bool(assumed.get("qkv_bias", False))
    window = cfg.get("sliding_window")
    if cfg.get("use_sliding_window") is False:
        window = None
    cfg["window"] = window
    cfg["pad_token_id"] = assumed.get("pad_token_id", 0)
    return cfg


def rehearsal_cut(cfg: dict) -> dict:
    """The CPU rehearsal's toy: same family flags, sizes a CPU decodes in
    seconds. Never used for a measurement."""
    cut = dict(cfg)
    cut.update(num_hidden_layers=2, hidden_size=256, num_attention_heads=8,
               num_key_value_heads=2 if cfg["num_key_value_heads"]
               < cfg["num_attention_heads"] else 8,
               intermediate_size=512, vocab_size=1024, head_dim=32)
    cut["eos_token_id"] = min(cfg["eos_token_id"], 2)
    return cut
