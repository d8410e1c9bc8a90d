"""The system under test, and nothing else of the program: the serving path
built as ``pipeline.backends.backend_for`` builds it (``DecodeEngine`` ->
``ServingBackend`` -> ``ContinuousScheduler`` -> ``stepbuilder`` programs),
from a configuration file's keys as its family reads them
(``perfbench/families``: the program's ``ModelConfig`` and the names of its
leaves), with weights the benchmark makes from the seed and a tokenizer that
hands the scheduler the token ids the traffic drew.

Spans: the benchmark wraps the scheduler's one call into its compiled
programs (``_run_compiled``) from outside, names each span by the program's
own label, and counts calls; spans inside the program are a later PR's.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Sequence

import numpy as np

from perfbench import families

from . import weights as W


class IdTokenizer:
    """The prompts are token ids already; the text is only their key."""

    def __init__(self, vocab_size: int, pad_id: int, eos_id: int):
        self.vocab_size, self.pad_id, self.eos_id = vocab_size, pad_id, eos_id
        self._ids: Dict[str, List[int]] = {}

    def register(self, key: str, ids: np.ndarray) -> None:
        self._ids[key] = [int(t) for t in ids]

    def forget(self, keys: Sequence[str]) -> None:
        for k in keys:
            self._ids.pop(k, None)

    def encode(self, text: str) -> List[int]:
        return list(self._ids[text])

    def decode(self, ids) -> str:
        return ""


def serving_config(mix: dict, pool: dict):
    """The program's ``ServingConfig``: its defaults, the cell's pool and
    prompt budget, then the cell's ``serving`` overrides by field name."""
    from fairness_llm_tpu.config import ServingConfig

    config = ServingConfig(
        enabled=True, num_slots=int(pool["num_slots"]),
        max_prompt_len=int(pool["prompt_budget"]),
        max_new_tokens=int(mix["output_tokens"]),
        queue_capacity=int(pool.get("queue_capacity", 128)),
    )
    overrides = pool.get("serving", {})
    known = {f.name for f in dataclasses.fields(ServingConfig)}
    unknown = sorted(set(overrides) - known)
    if unknown:
        raise ValueError(f"the cell's serving overrides name {unknown}, which "
                         "ServingConfig does not have")
    return dataclasses.replace(config, **overrides)


class ServedModel:
    def __init__(self, cfg: dict, name: str, mix: dict, pool: dict,
                 seed: int):
        from fairness_llm_tpu.config import ModelSettings
        from fairness_llm_tpu.runtime.engine import DecodeEngine
        from fairness_llm_tpu.serving import ServingBackend

        if float(mix.get("temperature", 0.0)) != 0.0:
            raise ValueError(
                "the comparison with the reference is valid for greedy "
                "tokens only; a mix with temperature > 0 has no check")
        self.cfg, self.mix = cfg, mix
        self.marks: Dict[str, float] = {}
        t0 = time.perf_counter()
        self.family = families.of(cfg)
        self._make_layer, self._make_globals = W.jitted_makers(cfg)
        params = self._make(seed)
        self.marks["weights_s"] = time.perf_counter() - t0
        self.tokenizer = IdTokenizer(cfg["vocab_size"], cfg["pad_token_id"],
                                     cfg["eos_token_id"])
        self.settings = ModelSettings(
            temperature=0.0, max_tokens=int(mix["output_tokens"]))
        t0 = time.perf_counter()
        self.engine = DecodeEngine(self.family.model_config(cfg, name),
                                   params=params, tokenizer=self.tokenizer,
                                   seed=seed & 0x7FFFFFFF)
        self.backend = ServingBackend(self.engine, serving_config(mix, pool),
                                      name=name)
        self.scheduler = self.backend.scheduler_for(self.settings)
        self.marks["build_s"] = time.perf_counter() - t0
        self.calls: List[tuple] = []  # (program label, host t0, host t1)
        self.annotate = False
        self._wrap_compiled_calls()

    # -- spans from outside ------------------------------------------------

    def _wrap_compiled_calls(self) -> None:
        import jax

        inner = self.scheduler._run_compiled

        def run_compiled(fn, *args):
            label = getattr(fn, "program", "unlabelled")
            t0 = time.perf_counter()
            if self.annotate:
                with jax.profiler.TraceAnnotation(label):
                    out = inner(fn, *args)
            else:
                out = inner(fn, *args)
            self.calls.append((label, t0, time.perf_counter()))
            return out

        self.scheduler._run_compiled = run_compiled

    def _make(self, seed: int) -> dict:
        """The served tree, on the device, in the types it is served in."""
        import jax

        words = W.seed_words(seed)
        tree = self.family.program_tree(
            self.cfg, self._make_globals(words),
            [self._make_layer(words, i)
             for i in range(len(self.family.layer_kinds(self.cfg)))])
        jax.block_until_ready(tree)
        return tree

    def span(self, name: str):
        """A phase span of the benchmark's own, in the trace when one is on."""
        import contextlib

        import jax

        if self.annotate:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def set_seed_weights(self, seed: int) -> None:
        """Remake the weights for another seed in place (calibration runs
        several seeds in one process)."""
        self.engine.params = None
        self.engine.params = self._make(seed)

    # -- counters ------------------------------------------------------------

    def new_stats(self):
        from fairness_llm_tpu.utils.profiling import ServingStats

        return ServingStats(num_slots=self.scheduler.num_slots)

    def totals(self) -> dict:
        """The backend's accumulated serving counters (closed passes)."""
        st = self.backend.serve_totals
        return {} if st is None else dict(st.as_dict())

    # -- closed batch: ServingBackend.generate as decode_sweep calls it -----

    def generate(self, specs, call_seed: int):
        for s in specs:
            self.tokenizer.register(s.id, s.ids)
        settings = dataclasses.replace(self.settings,
                                       max_tokens=int(specs[0].max_new))
        texts = self.backend.generate(
            [s.id for s in specs], settings, seed=call_seed,
            keys=[s.id for s in specs])
        toks = np.asarray(self.backend.last_output.tokens)
        self.tokenizer.forget([s.id for s in specs])
        out = []
        for i, s in enumerate(specs):
            ok = texts[i] is not None
            out.append((ok, _cut(toks[i], s.max_new, self.tokenizer.eos_id)
                        if ok else np.zeros((0,), np.int64)))
        return out

    # -- open loop: ContinuousScheduler.submit / step / take_result ---------

    def request(self, spec):
        from fairness_llm_tpu.serving.request import Request

        self.tokenizer.register(spec.id, spec.ids)
        return Request(prompt=spec.id, id=spec.id,
                       settings=dataclasses.replace(
                           self.settings, max_tokens=int(spec.max_new)),
                       row_seed=spec.row_seed & 0xFFFFFFFF)

    def free(self) -> None:
        """Drop every device buffer the program holds, so the reference has
        the chip to itself."""
        import gc

        import jax

        self.scheduler.release_memory()
        self.backend._schedulers.clear()
        self.scheduler._cache = None
        self.scheduler._prev_logits = None
        self.engine.params = None
        self.scheduler = self.backend = self.engine = None
        gc.collect()
        jax.clear_caches()


def _cut(row: np.ndarray, cap: int, eos_id: int) -> np.ndarray:
    row = np.asarray(row[:cap], np.int64)
    hit = np.flatnonzero(row == eos_id)
    return row[: hit[0] + 1] if hit.size else row
