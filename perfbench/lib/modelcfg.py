"""Reads a configuration file: the published keys at the top level, as the
model's own ``config.json`` names them, plus ``family`` (the kind of model,
``perfbench/families``; absent: ``dense_gqa``), ``assumed`` (sizes the source
does not give) and ``served`` (how the weights are stored and served). The
derived keys and the rehearsal's toy are the family's."""

from __future__ import annotations

import json

from perfbench import families


def load_config(path: str) -> dict:
    with open(path) as f:
        cfg = json.load(f)
    return families.of(cfg).load(cfg)


def rehearsal_cut(cfg: dict) -> dict:
    return families.of(cfg).rehearsal_cut(cfg)
