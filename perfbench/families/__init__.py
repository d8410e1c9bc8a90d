"""The kind of model a configuration runs. A family is one file here, named
by the configuration file's ``family`` key (absent: ``dense_gqa``); the
harness keeps what every model has in common and calls the family for the
rest. A new kind of model adds its file and edits none that is here.

What a family file provides, each a plain function of the configuration dict
(``cfg``: the configuration file's keys, then the family's derived ones):

``load(cfg)``
    The derived keys, written into ``cfg`` and returned. After it the harness
    reads ``vocab_size`` (the rows of the embedding and the head that the chip
    holds: traffic draws token ids under it), ``eos_token_id``,
    ``pad_token_id`` and ``initializer_range`` (default 0.02), and nothing
    else by name.
``rehearsal_cut(cfg)``
    The CPU rehearsal's toy: the same flags and kinds of layer at sizes a CPU
    decodes in seconds. Never measured.
``layer_kinds(cfg)``
    One kind name per layer, in order. The harness jits one weight maker and
    the reference one block per kind, with the layer index a traced argument.
``layer_leaves(cfg, kind)``, ``global_leaves(cfg)``
    ``(name, leaf kind, shape)`` in key order: leaf ``index`` of layer
    ``layer`` is made from ``weights._leaf_key(base, layer, index)``, so the
    order is part of the weights. The globals hold ``embedding``,
    ``final_norm`` and ``lm_head``, which the harness's reference reads.
``LEAF_MAKERS`` (optional)
    ``{leaf kind: maker(key, shape, sigma)}`` for kinds ``weights.make_leaf``
    lacks. A maker is a function of its key alone: the served tree and the
    reference's copy are the same numbers made twice.
``model_config(cfg, name)``, ``program_tree(cfg, glob, layers)``
    The program's ``ModelConfig``, and the made leaves under the names the
    program's model looks them up by.
``block(cfg, kind)``
    ``(fn, spec)`` for the reference: ``fn(x, layer, spec, ops)`` takes
    float32 ``[N, S, d]`` to the same and ``spec`` is a hashable tuple of its
    constants. Every matrix product's input goes through ``ops.dense(x,
    leaf)`` or ``ops.lower(x)`` (``reference.Ops``): that is the control, and
    a block that multiplies without them has none.
``model_flops(cfg, **stretch)``
    Model FLOPs of a traced stretch from the keys ``drivers.Window.traced``
    holds, from shapes and counts alone; it takes what it needs and ignores
    the rest (``**_``).
``STRETCH_COUNTERS`` (optional)
    Names of further counters of the program's ``ServingStats`` whose deltas
    the drivers copy into ``traced`` for ``model_flops``.
"""

from __future__ import annotations

import importlib
import os

DEFAULT = "dense_gqa"
_REQUIRED = ("load", "rehearsal_cut", "layer_kinds", "layer_leaves",
             "global_leaves", "model_config", "program_tree", "block",
             "model_flops")


def names() -> list:
    here = os.path.dirname(os.path.abspath(__file__))
    return sorted(f[:-3] for f in os.listdir(here)
                  if f.endswith(".py") and not f.startswith("_"))


def get(name: str):
    """The family's module. An unknown name raises with the ones that exist."""
    if name not in names():
        raise ValueError(f"no family {name!r} under perfbench/families: "
                         f"there are {names()}")
    family = importlib.import_module(f"{__name__}.{name}")
    missing = [f for f in _REQUIRED if not hasattr(family, f)]
    if missing:
        raise ValueError(f"family {name!r} lacks {missing}")
    return family


def of(cfg: dict):
    """The family of a configuration dict."""
    return get(cfg.get("family", DEFAULT))
