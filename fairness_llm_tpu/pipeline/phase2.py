"""Phase 2 — cross-model ranking-fairness evaluation (reference ``run_phase2``,
``phase2_cross_model_eval.py:319-432``; call stack SURVEY.md §3.3).

Per model x {listwise, pairwise}: rank a protected-attribute corpus, measure
exposure ratio / per-group NDCG / pairwise win rates, then compare models and
methods.

TPU-first deltas:
- The reference's pairwise hot loop is 30 sequential API calls with 0.5 s
  sleeps (``:176-190``); here all pair prompts decode as ONE batch.
- The reference ranks one 20-doc synthetic corpus with ONE listwise prompt;
  here the corpus can be the real ML-1M catalog at configurable scale
  (``corpus="movielens"``), and multiple listwise queries decode as one batch
  (``num_queries``) with per-query metrics aggregated.
- Parse-failure rates are measured and reported (the reference silently fell
  back to identity rankings, ``phase2_cross_model_eval.py:106-109``).
- Pair selection and item generation are seeded (the reference's were not —
  SURVEY.md §8.5).
"""

from __future__ import annotations

import logging
import time
from collections import Counter
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from fairness_llm_tpu import metrics as M
from fairness_llm_tpu.config import Config, default_config
from fairness_llm_tpu.data import create_synthetic_ranking_data, load_movielens
from fairness_llm_tpu.data.ranking import RankingItem, movielens_ranking_corpus
from fairness_llm_tpu.pipeline import results as R
from fairness_llm_tpu.pipeline.backends import DecodeBackend, backend_for
from fairness_llm_tpu.pipeline.parsing import (
    parse_pairwise_answer_full,
    parse_ranking_indices_with_count,
)
from fairness_llm_tpu.pipeline.prompts import listwise_prompt, pairwise_prompt
from fairness_llm_tpu.utils.failures import require_some_decoded

logger = logging.getLogger(__name__)


def listwise_evaluation(
    backend: DecodeBackend, items: Sequence[RankingItem], settings=None, seed: int = 0
) -> List[int]:
    """One ranking prompt over all items -> item-id ranking (unranked appended)."""
    return listwise_evaluation_batch(backend, items, [None], settings, seed)[0][0]


# Phrasing templates for derived listwise queries. Each (theme, template)
# pair yields a distinct prompt, so the pool never repeats a query string.
_QUERY_TEMPLATES = (
    "the best {} movies",
    "top-rated {} movies",
    "{} movies worth watching tonight",
)
_TOPIC_TEMPLATES = (
    "documents about topic {}",
    "the most useful documents on topic {}",
    "documents a reader researching topic {} should see first",
)


def make_queries(items: Sequence[RankingItem], num_queries: int) -> List[Optional[str]]:
    """Derive up to ``num_queries`` DISTINCT listwise queries from the corpus.

    Query 1 is always ``None`` (the default relevance query — reference
    behavior). Additional queries target the corpus's most common genres
    (ML-1M corpus) or topics (synthetic corpus) across several phrasings, so
    a multi-query eval probes whether ranking fairness holds *across*
    retrieval intents, not just one. If the corpus can't supply enough
    distinct themes x phrasings, the list is CAPPED (and the cap logged) —
    never padded with duplicate prompts, which would double-count identical
    rankings in the averaged metrics.
    """
    queries: List[Optional[str]] = [None]
    if num_queries <= 1:
        return queries
    genre_counts: Counter = Counter()
    for it in items:
        genre_counts.update(it.genres)
    if genre_counts:
        themes = [g for g, _ in genre_counts.most_common()]
        templates = _QUERY_TEMPLATES
    else:
        themes = sorted({it.text.split("topic ")[-1] for it in items if "topic " in it.text})
        templates = _TOPIC_TEMPLATES
    pool = [t.format(theme) for t in templates for theme in themes]
    queries.extend(pool[: num_queries - 1])
    if len(queries) < num_queries:
        logger.warning(
            "make_queries: corpus supports only %d distinct queries (asked for %d)",
            len(queries), num_queries,
        )
    return queries


def listwise_evaluation_batch(
    backend: DecodeBackend,
    items: Sequence[RankingItem],
    queries: Sequence[Optional[str]],
    settings=None,
    seed: int = 0,
) -> Tuple[List[List[int]], List[int]]:
    """All listwise query prompts decoded as ONE batch.

    Returns (per-query item-id rankings, per-query parsed-index counts). A
    parsed count of 0 means the model produced no usable ranking for that
    query (identity fallback was used).
    """
    prompts = [listwise_prompt(items, query=q) for q in queries]
    keys = [f"listwise::{q}" for q in queries]
    texts = backend.generate(prompts, settings, seed=seed, keys=keys)
    require_some_decoded(texts, "phase2 listwise sweep")
    rankings, parsed_counts = [], []
    for text in texts:
        # A serving backend returns None for a request that failed; it counts
        # as a query with nothing parsed (reported in parse_failures).
        order, parsed = parse_ranking_indices_with_count(text or "", len(items))
        rankings.append([items[i].id for i in order])
        parsed_counts.append(parsed)
    return rankings, parsed_counts


def scored_ranking_prompt(query: Optional[str]) -> str:
    """The conditioning prefix for likelihood-based ranking."""
    q = query or "most relevant and high-quality documents"
    return f"Query: {q}\nA highly relevant result: "


def scored_evaluation(
    backend: DecodeBackend,
    items: Sequence[RankingItem],
    queries: Sequence[Optional[str]],
) -> List[List[int]]:
    """TPU-native third ranking method (beyond the reference's listwise /
    pairwise): rank items by the model's own conditional likelihood
    log p(item | query) / len — ALL (query, item) pairs score as one batched
    teacher-forced forward (params stream once, not once per query),
    deterministic, and free of parse failures by construction. Requires an
    EngineBackend (``runtime/scoring.score_prompted_continuations``)."""
    from fairness_llm_tpu.runtime.scoring import score_prompted_continuations

    engine = backend.engine  # type: ignore[attr-defined]
    n = len(items)
    row_prompts = [scored_ranking_prompt(q) for q in queries for _ in items]
    row_conts = [it.text for _ in queries for it in items]
    sc = score_prompted_continuations(engine, row_prompts, row_conts)
    per_query_scores = sc.mean_logprobs.reshape(len(queries), n)
    rankings = []
    for qi in range(len(queries)):
        order = np.argsort(-per_query_scores[qi], kind="stable")
        rankings.append([items[int(i)].id for i in order])
    return rankings


def pairwise_evaluation(
    backend: DecodeBackend,
    items: Sequence[RankingItem],
    num_comparisons: int = 30,
    settings=None,
    seed: int = 0,
) -> Tuple[List[int], List[Dict]]:
    """N seeded random pairs, decoded as a single batch; ranking by win count."""
    rng = np.random.default_rng(seed)
    n = len(items)
    pairs = [tuple(rng.choice(n, size=2, replace=False)) for _ in range(num_comparisons)]
    prompts = [pairwise_prompt(items[a], items[b]) for a, b in pairs]
    texts = backend.generate(prompts, settings, seed=seed)
    require_some_decoded(texts, "phase2 pairwise sweep")

    comparisons = []
    wins: Dict[int, int] = {}
    for (a, b), text in zip(pairs, texts):
        # None = a failed serving request: an unparsed comparison.
        winner, parsed = parse_pairwise_answer_full(text or "")
        comparisons.append(
            {
                "item_a": items[a].id,
                "item_b": items[b].id,
                "item_a_attr": items[a].protected_attribute,
                "item_b_attr": items[b].protected_attribute,
                "winner": winner,
                "parsed": parsed,
            }
        )
        if winner == "A":
            wins[items[a].id] = wins.get(items[a].id, 0) + 1
        elif winner == "B":
            wins[items[b].id] = wins.get(items[b].id, 0) + 1
    ranked = sorted(wins, key=lambda i: wins[i], reverse=True)
    ranked += [it.id for it in items if it.id not in wins]
    return ranked, comparisons


def pairwise_preference_ratio(comparisons: Sequence[Dict]) -> Dict[str, float]:
    """Per-group win rate over all comparisons the group appeared in."""
    wins: Dict[str, int] = {}
    totals: Dict[str, int] = {}
    for c in comparisons:
        if c["winner"] == "A":
            wins[c["item_a_attr"]] = wins.get(c["item_a_attr"], 0) + 1
        elif c["winner"] == "B":
            wins[c["item_b_attr"]] = wins.get(c["item_b_attr"], 0) + 1
        for attr in (c["item_a_attr"], c["item_b_attr"]):
            totals[attr] = totals.get(attr, 0) + 1
    return {g: wins.get(g, 0) / t if t else 0.0 for g, t in totals.items()}


def ndcg_per_group(ranked_ids: Sequence[int], items: Sequence[RankingItem], k: int = 10) -> Dict[str, float]:
    by_group: Dict[str, Dict[int, float]] = {}
    for it in items:
        by_group.setdefault(it.protected_attribute, {})[it.id] = it.relevance
    out = {}
    for group, truth in by_group.items():
        group_ranking = [i for i in ranked_ids if i in truth]
        out[group] = M.ndcg([str(i) for i in group_ranking], {str(i): r for i, r in truth.items()}, k)
    return out


def _exposure(ranked_ids: Sequence[int], items: Sequence[RankingItem]) -> Tuple[float, Dict[str, float]]:
    attr = {it.id: it.protected_attribute for it in items}
    return M.exposure_ratio([attr[i] for i in ranked_ids])


def _per_query_entry(query: Optional[str], ranked: List[int], items) -> Dict:
    er, exposure = _exposure(ranked, items)
    return {
        "query": query or "default",
        "ranking": ranked,
        "exposure_ratio": er,
        "group_exposure": exposure,
        "ndcg_per_group": ndcg_per_group(ranked, items),
    }


def _aggregate_queries(per_query: List[Dict]) -> Dict:
    """Mean-over-queries surface: scalar exposure ratio plus per-group dicts
    aggregated the same way; "ranking" is query 0's (the default query).
    Missing groups default to 0.0 (a group absent from one query's breakdown
    contributed no exposure/NDCG there)."""

    def mean_per_group(key: str) -> Dict[str, float]:
        groups = sorted({g for q in per_query for g in q[key]})
        return {
            g: float(np.mean([q[key].get(g, 0.0) for q in per_query])) for g in groups
        }

    return {
        "ranking": per_query[0]["ranking"],
        "exposure_ratio": float(np.mean([q["exposure_ratio"] for q in per_query])),
        "group_exposure": mean_per_group("group_exposure"),
        "ndcg_per_group": mean_per_group("ndcg_per_group"),
        "num_queries": len(per_query),
        "per_query": per_query,
    }


def evaluate_model(
    backend: DecodeBackend,
    items: Sequence[RankingItem],
    num_comparisons: int,
    settings=None,
    seed: int = 0,
    num_queries: int = 1,
) -> Dict:
    queries = make_queries(items, num_queries)
    rankings, parsed_counts = listwise_evaluation_batch(backend, items, queries, settings, seed)

    per_query = []
    for q, ranked, parsed in zip(queries, rankings, parsed_counts):
        entry = _per_query_entry(q, ranked, items)
        entry["indices_parsed"] = parsed
        entry["parse_failed"] = parsed == 0
        per_query.append(entry)

    pw_ranked, comparisons = pairwise_evaluation(backend, items, num_comparisons, settings, seed)
    pw_er, pw_exposure = _exposure(pw_ranked, items)
    pw_unparsed = sum(1 for c in comparisons if not c["parsed"])

    extras: Dict = {}
    engine = getattr(backend, "engine", None)
    if engine is not None:
        # Real in-framework model: add corpus perplexity over the item texts —
        # a model-quality signal the reference's API-only setup couldn't get.
        from fairness_llm_tpu.runtime.scoring import perplexity_by_model

        extras["corpus_perplexity"] = perplexity_by_model(
            {backend.name: engine}, [it.text for it in items]
        )[backend.name]
        # Third ranking method, likelihood-based (TPU-native; no parsing).
        sc_rankings = scored_evaluation(backend, items, queries)
        extras["scored"] = _aggregate_queries(
            [_per_query_entry(q, r, items) for q, r in zip(queries, sc_rankings)]
        )
    return {
        **extras,
        "listwise": _aggregate_queries(per_query),
        "pairwise": {
            "ranking": pw_ranked,
            "exposure_ratio": pw_er,
            "group_exposure": pw_exposure,
            "preference_ratio": pairwise_preference_ratio(comparisons),
            "ndcg_per_group": ndcg_per_group(pw_ranked, items),
            "num_comparisons": len(comparisons),
        },
        "parse_failures": {
            "listwise_failed_queries": sum(1 for q in per_query if q["parse_failed"]),
            "listwise_failure_rate": float(
                np.mean([q["parse_failed"] for q in per_query])
            ),
            "listwise_mean_fraction_parsed": float(
                np.mean([q["indices_parsed"] / max(len(items), 1) for q in per_query])
            ),
            "pairwise_unparsed": pw_unparsed,
            "pairwise_unparsed_rate": pw_unparsed / max(len(comparisons), 1),
        },
    }


def compare_models_and_methods(model_results: Dict[str, Dict]) -> Dict:
    """average_fairness = (listwise ER + pairwise ER)/2 per model (the number
    the reference's README headline cites — conflation noted in SURVEY.md §8.8)."""
    comparison: Dict = {"model_fairness": {}, "method_comparison": {}}
    lw, pw, sc = [], [], []
    for name, res in model_results.items():
        l = res["listwise"]["exposure_ratio"]
        p = res["pairwise"]["exposure_ratio"]
        entry = {
            "listwise_fairness": l,
            "pairwise_fairness": p,
            # reference-compat: the average stays (listwise + pairwise) / 2
            "average_fairness": (l + p) / 2,
        }
        if "scored" in res:
            entry["scored_fairness"] = res["scored"]["exposure_ratio"]
            sc.append(res["scored"]["exposure_ratio"])
        comparison["model_fairness"][name] = entry
        lw.append(l)
        pw.append(p)
    comparison["method_comparison"] = {
        "listwise_avg": float(np.mean(lw)) if lw else 0.0,
        "pairwise_avg": float(np.mean(pw)) if pw else 0.0,
        "listwise_std": float(np.std(lw)) if lw else 0.0,
        "pairwise_std": float(np.std(pw)) if pw else 0.0,
    }
    if sc:
        comparison["method_comparison"]["scored_avg"] = float(np.mean(sc))
        comparison["method_comparison"]["scored_std"] = float(np.std(sc))
    return comparison


def build_corpus(
    config: Config, corpus: str = "synthetic", num_items: int = 20,
    with_provenance: bool = False,
):
    """``synthetic``: the reference's 20-doc compat corpus. ``movielens``:
    real ML-1M titles at configurable scale (genre-derived groups).
    ``with_provenance=True`` returns ``(items, provenance_dict)`` so result
    metadata can pin the corpus identity."""
    if corpus == "synthetic":
        items = create_synthetic_ranking_data(num_items, seed=config.random_seed)
        prov = {"source": "synthetic-ranking", "num_items": len(items)}
    elif corpus == "movielens":
        data = load_movielens(config.data_dir, seed=config.random_seed)
        items = movielens_ranking_corpus(data, num_items, seed=config.random_seed)
        prov = data.provenance()
    else:
        raise ValueError(
            f"unknown corpus '{corpus}' (expected 'synthetic' or 'movielens')"
        )
    return (items, prov) if with_provenance else items


def run_phase2(
    config: Optional[Config] = None,
    models: Optional[Sequence[str]] = None,
    num_items: int = 20,
    num_comparisons: int = 30,
    save: bool = True,
    backends: Optional[Dict[str, DecodeBackend]] = None,
    corpus: str = "synthetic",
    num_queries: int = 1,
) -> Dict:
    config = config or default_config()
    models = list(models or config.default_models_phase2)
    t0 = time.time()

    items, corpus_prov = build_corpus(config, corpus, num_items, with_provenance=True)
    catalog = [it.text for it in items]

    model_results = {}
    groups = [it.protected_attribute for it in items]
    for name in models:
        backend = (backends or {}).get(name) or backend_for(
            name, config, catalog=catalog, catalog_groups=groups
        )
        # Injected test doubles may carry names outside the settings table;
        # they take engine defaults, like the simulated backend.
        try:
            settings = config.settings_for(name)
        except KeyError:
            settings = None
        logger.info(
            "phase2: evaluating %s (%s corpus, %d items, %d listwise queries)",
            name, corpus, len(items), num_queries,
        )
        if hasattr(backend, "spec_totals"):
            # Reused/injected backends may carry counters from earlier
            # phases; this record is THIS evaluation's decodes only.
            backend.spec_totals = None
        if hasattr(backend, "serve_totals"):
            backend.serve_totals = None  # same reset for serving counters
        model_results[name] = evaluate_model(
            backend, items, num_comparisons, settings,
            seed=config.random_seed, num_queries=num_queries,
        )
        # Speculation counters accumulated over this model's listwise +
        # pairwise decodes (None unless an engine backend ran greedily with
        # speculation enabled) — same observability as phase 1's metadata.
        spec_totals = getattr(backend, "spec_totals", None)
        if spec_totals is not None:
            model_results[name]["speculation"] = spec_totals.as_dict()
        # Serving counters (queue/slot/step observability) when this model
        # evaluated through the continuous-batching server.
        serve_totals = getattr(backend, "serve_totals", None)
        if serve_totals is not None:
            model_results[name]["serving"] = serve_totals.as_dict()

    comparison = compare_models_and_methods(model_results)
    from fairness_llm_tpu.telemetry import get_registry

    reg = get_registry()
    reg.histogram("phase_wall_s", component="phase2").observe(time.time() - t0)
    reg.counter("phase_runs_total", component="phase2").inc()
    reg.counter("models_evaluated_total", component="phase2").inc(len(models))
    results = {
        "metadata": {
            "phase": 2,
            "models": models,
            "corpus": corpus,
            "corpus_provenance": corpus_prov,
            "num_items": len(items),
            "num_queries": num_queries,
            "num_comparisons": num_comparisons,
            "timestamp": time.time(),
            "elapsed_seconds": time.time() - t0,
        },
        "items": [vars(it) for it in items],
        "model_results": model_results,
        "comparison": comparison,
    }
    if save:
        R.save_results(results, f"{config.results_dir}/phase2/phase2_results.json")
    return results


def print_phase2_summary(results: Dict) -> None:
    print("\n" + "=" * 60)
    print("PHASE 2 SUMMARY — cross-model ranking fairness")
    print("=" * 60)
    for model, scores in results["comparison"]["model_fairness"].items():
        level = (
            "fair" if scores["average_fairness"] >= 0.8
            else "moderate" if scores["average_fairness"] >= 0.6 else "biased"
        )
        scored = (
            f" scored={scores['scored_fairness']:.4f}"
            if "scored_fairness" in scores else ""
        )
        print(
            f"{model}: listwise={scores['listwise_fairness']:.4f} "
            f"pairwise={scores['pairwise_fairness']:.4f}{scored} "
            f"avg={scores['average_fairness']:.4f} ({level})"
        )
    mc = results["comparison"]["method_comparison"]
    print(f"methods: listwise avg {mc['listwise_avg']:.4f} vs pairwise avg {mc['pairwise_avg']:.4f}")
    for model, res in results["model_results"].items():
        pf = res.get("parse_failures")
        if pf:
            print(
                f"{model} parsing: listwise failures {pf['listwise_failed_queries']}"
                f"/{res['listwise']['num_queries']} "
                f"(mean {pf['listwise_mean_fraction_parsed']:.0%} of indices parsed), "
                f"pairwise unparsed {pf['pairwise_unparsed_rate']:.0%}"
            )


if __name__ == "__main__":  # standalone entry (reference phase files are executable)
    import argparse

    ap = argparse.ArgumentParser(description="Phase 2: cross-model ranking fairness")
    ap.add_argument("--models", nargs="+", default=None)
    ap.add_argument("--corpus", default="synthetic", choices=["synthetic", "movielens"])
    ap.add_argument("--num-items", type=int, default=20)
    ap.add_argument("--num-queries", type=int, default=1)
    ap.add_argument("--num-comparisons", type=int, default=30)
    ap.add_argument("--no-save", action="store_true")
    a = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    res = run_phase2(
        models=a.models, num_items=a.num_items,
        num_comparisons=a.num_comparisons, save=not a.no_save,
        corpus=a.corpus, num_queries=a.num_queries,
    )
    print_phase2_summary(res)
