#!/usr/bin/env python3
"""How often the program and the plain reference choose different experts.

For each seed: the configuration's weights, a batch of token ids drawn as the
cell's traffic draws them, one forward of the program's model (its own
precision, no cache) that keeps every expert layer's chosen experts, and the
reference's forward in float32 keeping its own. Counted per (position, expert
layer): the two sets of experts differ; and, of those, a held expert is in
one set and not the other (only such a flip changes what this chip computes).
Prints one JSON line per seed. A diagnostic beside ``correct``, which it does
not feed: run it on the chip at the cell's sizes.

    python3 perfbench/tools/router_flips.py --workload <cell> --seeds 1,2
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "perfbench"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rows", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    import run as bench  # perfbench/run.py

    c, _ = bench.prepare(a.workload, a.rehearse)
    cfg, mix = c["cfg"], c["mix"]
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fairness_llm_tpu.models.transformer import Transformer
    from perfbench import families
    from perfbench.lib import reference, traffic
    from perfbench.lib import weights as W

    family = families.of(cfg)
    kinds = family.layer_kinds(cfg)
    model = Transformer(family.model_config(cfg, c["cell"]["config"]))
    make_layer, make_globals = W.jitted_makers(cfg)
    first = cfg["expert_rank"] * cfg["n_routed_experts"]
    held = np.arange(first, first + cfg["n_routed_experts"])

    @jax.jit
    def program(params, tokens):
        positions = jnp.tile(jnp.arange(tokens.shape[1])[None],
                             (tokens.shape[0], 1))
        _, state = model.apply({"params": params}, tokens, positions,
                               mutable=["intermediates"])
        return [state["intermediates"][f"layer_{i}"]["mlp"]["chosen"][0]
                for i, kind in enumerate(kinds) if kind == "expert_layer"]

    blocks = {kind: family.block(cfg, kind) for kind in dict.fromkeys(kinds)}
    route = jax.jit(
        lambda x, layer, spec: family.routing(x, layer, spec,
                                              reference.Ops())[2],
        static_argnames=("spec",))
    for seed in (int(s) for s in a.seeds.split(",")):
        specs = traffic.closed_pass(dict(mix, pass_size=a.rows), seed, 0,
                                    cfg["vocab_size"])
        length = -(-max(len(s.ids) for s in specs) // 128) * 128
        tokens = np.zeros((a.rows, length), np.int32)
        real = np.zeros((a.rows, length), bool)
        for r, s in enumerate(specs):
            tokens[r, : len(s.ids)] = s.ids
            real[r, : len(s.ids)] = True
        words = W.seed_words(seed)
        glob = make_globals(words)
        layers = [make_layer(words, i) for i in range(len(kinds))]
        got = [np.asarray(x) for x in program(
            family.program_tree(cfg, glob, layers), jnp.asarray(tokens))]
        del layers
        want = []
        with jax.default_matmul_precision("highest"):
            x = glob["embedding"][jnp.asarray(tokens)].astype(jnp.float32)
            for i, kind in enumerate(kinds):
                layer = make_layer(words, i)
                if kind == "expert_layer":
                    want.append(np.asarray(route(x, layer, blocks[kind][1])))
                x = reference._block(x, layer, *blocks[kind], None)
        differ = local = 0
        for g, w in zip(got, want):
            changed = (np.sort(g, axis=-1) != np.sort(w, axis=-1)).any(-1) \
                & real
            mine = ((g[..., None] == held).any(-2)
                    != (w[..., None] == held).any(-2)).any(-1)
            differ += int(changed.sum())
            local += int((changed & mine).sum())
        print(json.dumps({
            "workload": a.workload, "seed": seed,
            "positions_x_expert_layers": int(real.sum()) * len(got),
            "chose_other_experts": differ,
            "of_which_a_held_expert_changed": local}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
