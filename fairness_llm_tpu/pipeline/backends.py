"""Decode backends: the seam where phase logic meets model inference.

The reference hard-wires ``client.chat.completions.create`` into every phase
driver (SURVEY.md §1 layer 3). Here the seam is an explicit protocol with two
implementations:

- ``EngineBackend`` — the real path: batched sharded decode on TPU via
  ``runtime.DecodeEngine``. One call = one device program over the whole
  prompt batch (vs. the reference's N sequential HTTPS round-trips).
- ``SimulatedRecommender`` — the deterministic fake backend the reference never
  had (SURVEY.md §4 calls this out as the natural test strategy): seeded,
  instant, with an injectable demographic-bias knob so fairness metrics are
  non-trivial and mitigation measurably works. Powers tests and ``--quick``
  runs without weights.
"""

from __future__ import annotations

import hashlib
import logging
import re
from typing import List, Optional, Protocol, Sequence

import numpy as np

from fairness_llm_tpu.config import Config, ModelSettings

logger = logging.getLogger(__name__)


class DecodeBackend(Protocol):
    """``keys`` are optional stable per-prompt identities (profile ids): a
    deterministic backend must key its per-prompt randomness on them — not on
    batch position — so resumed sweeps reproduce uninterrupted ones."""

    name: str

    def generate(
        self,
        prompts: Sequence[str],
        settings: Optional[ModelSettings] = None,
        seed: int = 0,
        keys: Optional[Sequence[str]] = None,
        prefix_ids: Optional[Sequence[int]] = None,
    ) -> List[str]:
        ...


class EngineBackend:
    """Real in-framework decode.

    ``speculation`` (a ``SpeculationConfig``) turns on prompt-lookup
    speculative decoding for greedy sweeps; the engine falls back to the
    plain path for sampled settings, so it is always safe to set. Per-sweep
    counters accumulate in ``spec_totals`` (a ``SpeculationStats``) so phase
    drivers can record acceptance in their result metadata.
    """

    def __init__(self, engine, name: Optional[str] = None, speculation=None):
        self.engine = engine
        self.name = name or engine.config.name
        self.speculation = speculation
        self.spec_totals = None  # Optional[SpeculationStats], set lazily

    def generate(
        self,
        prompts: Sequence[str],
        settings: Optional[ModelSettings] = None,
        seed: int = 0,
        keys: Optional[Sequence[str]] = None,
        prefix_ids: Optional[Sequence[int]] = None,
    ) -> List[str]:
        row_seeds = None
        if keys is not None:
            # Per-row sampling streams keyed on stable identity, so resumed /
            # re-chunked sweeps decode identical text for the same profile.
            row_seeds = [(_stable_hash(k) ^ seed) & 0xFFFFFFFF for k in keys]
        out = self.engine.generate(
            prompts, settings, seed=seed, row_seeds=row_seeds,
            prefix_ids=prefix_ids,
            # sweeps pass an explicit sweep-wide prefix; never auto-detect per
            # chunk (composition-dependent — see engine.generate docstring)
            share_prefix=None if prefix_ids is not None else False,
            speculation=self.speculation,
        )
        sp = (out.stats or {}).get("speculation")
        if sp is not None:
            from fairness_llm_tpu.utils.profiling import SpeculationStats

            chunk = SpeculationStats.from_dict(sp)
            self.spec_totals = (
                chunk if self.spec_totals is None
                else self.spec_totals.merge(chunk)
            )
        return out.texts


def shared_prefix_ids(backend, prompts: Sequence[str]) -> Optional[List[int]]:
    """Sweep-wide shared prefix for reproducible prefix-cached decode: the
    longest common token prefix over ALL the sweep's prompts, floored to a
    multiple of 64 (compile-shape reuse). None for non-engine backends or
    short prefixes. Computing this once over the full sweep — instead of per
    chunk — keeps resumed runs numerically identical to uninterrupted ones."""
    engine = getattr(backend, "engine", None)
    if engine is None or len(prompts) < 2:
        return None
    if not getattr(backend, "use_shared_prefix", True):
        # ServingBackend decodes rows independently and ignores prefix_ids;
        # tokenizing the whole sweep for an unused LCP is pure waste.
        return None
    from fairness_llm_tpu.runtime.engine import _token_lcp

    rows = [engine.tokenizer.encode(p) for p in prompts]
    common = (_token_lcp(rows) // 64) * 64
    return list(rows[0][:common]) if common >= 64 else None


def _stable_hash(*parts: object) -> int:
    h = hashlib.sha256("||".join(str(p) for p in parts).encode()).digest()
    return int.from_bytes(h[:8], "little")


_GENDER_RE = re.compile(r"Gender:\s*([\w\-]+)", re.IGNORECASE)
_AGE_RE = re.compile(r"Age Group:\s*([\w\-\+]+)", re.IGNORECASE)


class SimulatedRecommender:
    """Deterministic prompt-shape-aware fake model.

    Recommendation prompts: picks 10 titles from a seeded global shuffle of the
    catalog, sliding the selection window by a demographic-dependent offset
    scaled by ``bias`` — so counterfactual profiles get measurably different
    recommendations. When the prompt carries a fairness instruction block the
    offset shrinks by ``mitigation`` (fair prompting "works"), letting phase 3
    demonstrate real bias reduction end to end.

    Listwise prompts ("Your ranking:"): seeded permutation — biased when
    ``catalog_groups`` is supplied: items of the preferred group get a score
    boost proportional to ``bias``, so ranking-fairness metrics (exposure
    ratio, per-group NDCG) measurably respond to the knob and two variants
    with different bias levels give phase 2 a real cross-model comparison
    (the reference compares gpt-3.5 vs gpt-4 the same way).
    Pairwise prompts ("Your answer:"): seeded A/B choice, group-biased under
    the same rule. ``bias`` is calibrated for [0, 1]; at >= 1 both methods
    saturate (preferred group ranks fully on top / always wins comparisons).
    """

    def __init__(
        self,
        catalog: Sequence[str],
        seed: int = 42,
        bias: float = 0.6,
        mitigation: float = 0.85,
        name: str = "simulated",
        catalog_groups: Optional[Sequence[str]] = None,
    ):
        if not catalog:
            raise ValueError("SimulatedRecommender needs a non-empty catalog")
        if catalog_groups is not None and len(catalog_groups) != len(catalog):
            raise ValueError("catalog_groups must align with catalog")
        self.catalog = list(catalog)
        self.seed = seed
        self.bias = bias
        self.mitigation = mitigation
        self.name = name
        # Keyed on stripped title text (the ranking regexes strip whitespace);
        # positional fallback in _rank covers duplicate/colliding titles.
        self._groups = list(catalog_groups) if catalog_groups else []
        self._group_of = {}
        for text, group in zip(self.catalog, self._groups):
            key = text.strip()
            if key in self._group_of and self._group_of[key] != group:
                logger.warning(
                    "SimulatedRecommender: duplicate catalog title %r with "
                    "conflicting groups; listwise prompts use exact positional "
                    "mapping, pairwise text lookup keeps the last assignment",
                    key,
                )
            self._group_of[key] = group
        # The "preferred" group the biased ranker over-exposes: first group in
        # sorted order — arbitrary but deterministic.
        self._preferred = sorted(set(catalog_groups))[0] if catalog_groups else None
        order = sorted(
            range(len(self.catalog)), key=lambda i: _stable_hash(self.catalog[i], seed)
        )
        self._shuffled = [self.catalog[i] for i in order]

    # -- prompt-shape handlers ----------------------------------------------

    def _recommend(self, prompt: str, idx: int, seed: int, n: int = 10) -> str:
        gender = (_GENDER_RE.search(prompt) or [None, "neutral"])[1].lower()
        age = (_AGE_RE.search(prompt) or [None, "neutral"])[1].lower()
        fair = "FAIRNESS REQUIREMENT" in prompt or "FAIRNESS PROTOCOL" in prompt
        bias = self.bias * (1.0 - self.mitigation) if fair else self.bias
        group_key = _stable_hash(gender, age) % 7
        offset = int(round(bias * 4 * group_key)) % max(len(self._shuffled) - 2 * n, 1)
        rng = np.random.default_rng([self.seed & 0x7FFFFFFF, seed & 0x7FFFFFFF, idx])
        window = self._shuffled[offset : offset + int(n * 1.5)]
        take = min(n, len(window))
        chosen = list(rng.choice(len(window), size=take, replace=False))
        titles = [window[c] for c in chosen]
        return "\n".join(f"{i + 1}. {t}" for i, t in enumerate(titles))

    def _rank(self, prompt: str, idx: int, seed: int) -> str:
        lines = re.findall(r"^\d+\.\s*(.+?)\s*$", prompt, flags=re.MULTILINE)
        num_items = max(len(lines), 1)
        rng = np.random.default_rng([self.seed & 0x7FFFFFFF, seed & 0x7FFFFFFF, idx, 1])
        if not self._group_of:  # group-blind: plain seeded permutation
            perm = rng.permutation(num_items) + 1
            return ",".join(str(int(p)) for p in perm)
        # Group-biased ranking: preferred-group items float up by up to
        # ``bias`` (saturated at >= 1: preferred scores in [bias, 1+bias) are
        # then disjoint from non-preferred [0, 1)). A full-catalog prompt (the
        # listwise case: items enumerated in catalog order) uses POSITIONAL
        # group mapping — exact even for duplicate titles; other prompts fall
        # back to title-text lookup.
        positional_ok = len(lines) == len(self._groups)
        scores = rng.random(num_items)
        for i, text in enumerate(lines):
            group = self._groups[i] if positional_ok else self._group_of.get(text)
            if group == self._preferred:
                scores[i] += self.bias
        order = np.argsort(-scores, kind="stable") + 1
        return ",".join(str(int(p)) for p in order)

    def _compare(self, prompt: str, idx: int, seed: int) -> str:
        rng = np.random.default_rng(
            [_stable_hash(prompt) & 0x7FFFFFFF, self.seed & 0x7FFFFFFF, seed & 0x7FFFFFFF]
        )
        if self._group_of:
            m = re.search(r"Document A:\s*(.+?)\s*\n+Document B:\s*(.+?)\s*\n", prompt)
            if m:
                ga = self._group_of.get(m.group(1))
                gb = self._group_of.get(m.group(2))
                if ga != gb and self._preferred in (ga, gb):
                    # Prefer the preferred-group item with prob 0.5 + bias/2,
                    # clamped: past bias=1 the pairwise preference saturates
                    # at always-preferred while listwise keeps separating.
                    p_pref = min(1.0, 0.5 + self.bias / 2)
                    pick_pref = rng.random() < p_pref
                    pref_is_a = ga == self._preferred
                    return "A" if pick_pref == pref_is_a else "B"
        return "A" if rng.random() < 0.5 else "B"

    def generate(
        self,
        prompts: Sequence[str],
        settings: Optional[ModelSettings] = None,
        seed: int = 0,
        keys: Optional[Sequence[str]] = None,
        prefix_ids: Optional[Sequence[int]] = None,  # unused: text-level sim
    ) -> List[str]:
        # Entropy per prompt = (seed, prompt hash, stable key) — NOT batch
        # position — so outputs don't depend on how the sweep was chunked or
        # which already-done prompts a resume skipped. The key distinguishes
        # repeated identical prompts (same demographic combo, different
        # profile); without keys, occurrence order within the call stands in.
        out = []
        seen: dict = {}
        for i, p in enumerate(prompts):
            if keys is not None:
                salt = _stable_hash(keys[i])
            else:
                occ = seen.get(p, 0)
                seen[p] = occ + 1
                salt = occ
            idx = (_stable_hash(p) + salt) & 0x7FFFFFFF
            if "Your ranking:" in p:
                out.append(self._rank(p, idx, seed))
            elif "Your answer:" in p:
                out.append(self._compare(p, idx, seed))
            else:
                out.append(self._recommend(p, idx, seed))
        return out


# Named simulated variants: distinct bias levels make cross-model phase-2
# comparison non-vacuous without weights (e.g. --models simulated-fair
# simulated-biased mirrors the reference's gpt-3.5 vs gpt-4 comparison).
SIMULATED_VARIANTS = {"simulated": 0.6, "simulated-fair": 0.15, "simulated-biased": 0.9}


def backend_for(
    model_name: str,
    config: Config,
    catalog: Optional[Sequence[str]] = None,
    params=None,
    allow_random: bool = False,
    catalog_groups: Optional[Sequence[str]] = None,
) -> DecodeBackend:
    """Resolve a model name to a backend.

    'simulated' (or a ``SIMULATED_VARIANTS`` name) -> SimulatedRecommender.
    A real model name builds a DecodeEngine with HF weights from
    ``config.weights_dir/<model_name>``. When no weights exist the call FAILS
    rather than silently sweeping with randomly initialized weights and
    labeling the results with the model's name — pass ``allow_random=True``
    (smoke tests, benchmarks) to opt in.
    """
    if model_name in SIMULATED_VARIANTS:
        return SimulatedRecommender(
            catalog or [], seed=config.random_seed,
            bias=SIMULATED_VARIANTS[model_name], name=model_name,
            catalog_groups=catalog_groups,
        )

    import os

    from fairness_llm_tpu.models.configs import get_model_config
    from fairness_llm_tpu.parallel import make_mesh
    from fairness_llm_tpu.runtime.engine import DecodeEngine

    model_config = get_model_config(model_name)
    serving = getattr(config, "serving", None)
    use_serving = serving is not None and serving.enabled
    if use_serving and (config.mesh.dp > 1 or config.mesh.sp > 1):
        # Fail BEFORE the mesh is built and a (possibly sharded) checkpoint
        # is loaded — the scheduler would reject the mesh at construction
        # anyway, minutes of weight loading later. Tensor-parallel-only
        # meshes (--tp N) DO compose with serving: the scheduler shards the
        # slot cache on kv heads and runs every program SPMD over the mesh.
        raise ValueError(
            "--continuous serving supports single-device or tp-only meshes "
            "(the KV slot scatter is not dp/sp-aware yet); use --tp N or "
            "drop --mesh"
        )
    import dataclasses as _dc

    if use_serving:
        # A KV-slot row holds the prompt bucket plus the decode cap, so the
        # cap is bounded by what requests can ask for (--max-new-tokens
        # clamps every model's max_tokens) and by half the model's positions
        # — the scheduler refuses a cap that leaves no prompt budget, which
        # would fail every row of a small model's sweep on the DEFAULT cap.
        cap = min(serving.max_new_tokens, config.max_new_tokens,
                  model_config.max_seq_len // 2)
        if cap != serving.max_new_tokens:
            serving = _dc.replace(serving, max_new_tokens=cap)
    if getattr(config, "weight_quant", None) is not None:
        # Explicit override in EITHER direction: "int8" quantizes a float
        # config, "none" forces float serving for e.g. llama3-70b-int8.
        model_config = _dc.replace(model_config, weight_quant=config.weight_quant)
    mesh = None
    if config.mesh.num_devices > 1:
        mesh = make_mesh(config.mesh)
    from fairness_llm_tpu.config import IntegrityConfig

    integrity = getattr(config, "integrity", None) or IntegrityConfig()
    ckpt = os.path.join(config.weights_dir or "", model_name)
    tokenizer_path = None
    loaded_params = params
    loaded_sharded = False
    if params is None and config.weights_dir and os.path.isdir(ckpt):
        from fairness_llm_tpu.runtime.weights import load_checkpoint

        logger.info("loading %s weights from %s", model_name, ckpt)
        # Manifest-verified load (integrity/): a bit-flipped or truncated
        # shard is refused HERE, naming the file — never served.
        loaded_params = load_checkpoint(
            model_config, ckpt, mesh=mesh,
            verify=integrity.verify_manifests,
        )
        loaded_sharded = mesh is not None
        if os.path.exists(os.path.join(ckpt, "tokenizer_config.json")):
            tokenizer_path = ckpt
    if loaded_params is None and not allow_random:
        raise FileNotFoundError(
            f"no weights for '{model_name}' under weights_dir="
            f"{config.weights_dir!r}; use --model simulated, provide a "
            f"checkpoint, or pass allow_random=True for a smoke run"
        )
    engine = DecodeEngine(
        model_config,
        params=loaded_params,
        mesh=mesh,
        tokenizer_path=tokenizer_path,
        seed=config.random_seed,
        assume_sharded=loaded_sharded,
        numerics_guards=integrity.numerics_guards,
    )
    resilience = getattr(config, "resilience", None)
    if resilience is not None and not resilience.enabled:
        resilience = None
    if use_serving:
        # Continuous-batching server (--continuous): same DecodeBackend
        # surface, slot-recycled decode underneath. Single-device or a
        # tp-only mesh (dp/sp rejected above, before the weight load);
        # speculation doesn't compose with the step-wise serving loop yet,
        # so it is ignored.
        from fairness_llm_tpu.serving import ServingBackend

        journal = None
        if resilience is not None and resilience.journal_dir:
            from fairness_llm_tpu.resilience import ServingJournal

            journal = ServingJournal(
                resilience.journal_dir,
                rotate_every=resilience.journal_rotate_every,
            )
        return ServingBackend(engine, serving, name=model_name,
                              resilience=resilience, journal=journal,
                              integrity=integrity,
                              fleet=getattr(config, "fleet", None),
                              overload=getattr(config, "overload", None),
                              autoscale=getattr(config, "autoscale", None))
    # Speculation rides on the backend (not the engine default) so sweeps
    # opted in via Config get it while direct engine users stay explicit.
    spec = getattr(config, "speculation", None)
    if resilience is not None:
        # Engine-only path still gets the watchdog (hang classification on
        # generate calls, contained by with_failure_containment) and a
        # board for the speculate gate.
        from fairness_llm_tpu.resilience import BreakerBoard, StepWatchdog

        engine.breakers = BreakerBoard(
            failure_threshold=resilience.breaker_threshold,
            cooldown_s=resilience.breaker_cooldown_s,
            component="engine",
        )
        if resilience.max_step_seconds > 0:
            engine.watchdog = StepWatchdog(
                resilience.max_step_seconds, component="engine"
            )
    return EngineBackend(
        engine, name=model_name,
        speculation=spec if (spec is not None and spec.enabled) else None,
    )
