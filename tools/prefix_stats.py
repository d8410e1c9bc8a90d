"""Pre-run prefix-reuse report for a sweep's prompt set.

Builds the phase-1-shaped prompt set (profile grid -> counterfactual
recommendation prompts, optionally the phase-3 fairness-aware variants),
tokenizes it, and reckons what the serving path's admission would copy
from a live row instead of prefilling (``slots.LiveRowPrefixes`` + the
extend ``serve_prefill``) — so the expected reuse share and the
longest-common-prefix histogram are inspectable BEFORE paying for a run.

Usage:
    python tools/prefix_stats.py                  # stock grid
    python tools/prefix_stats.py --profiles 5
    python tools/prefix_stats.py --phase 3 --variant smart
    python tools/prefix_stats.py --json stats.json

The share is the most a run can reuse: each prompt is matched against every
EARLIER prompt of the sweep as if that row were still resident, and copies
its longest common prefix less the whole ``EXTEND_WINDOW``-token windows it
must still run. A run reuses less where the sharer has left the pool or
sits in the same admission group (the ledger's cells read 54-62% against
0.905 shared). Tokenization is byte-level (``ByteTokenizer``) — real
checkpoints tokenize coarser, which SHIFTS absolute token counts but
barely moves the shared FRACTION (the quantity the reuse share rides on).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import Counter

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fairness_llm_tpu.config import default_config  # noqa: E402
from fairness_llm_tpu.data.movielens import load_movielens  # noqa: E402
from fairness_llm_tpu.data.profiles import (  # noqa: E402
    create_base_preferences,
    create_profile_grid,
    profile_pairs,
)
from fairness_llm_tpu.models.tokenizer import ByteTokenizer  # noqa: E402
from fairness_llm_tpu.pipeline.prompts import (  # noqa: E402
    divergence_stats,
    fairness_aware_prompt,
    lcp_len,
    recommendation_prompt,
)
from fairness_llm_tpu.runtime.stepbuilder import EXTEND_WINDOW  # noqa: E402


def expected_reuse(token_rows):
    """Per prompt, the positions admission would copy from a live row if
    every earlier prompt of the sweep were still resident — the plan of
    ``ContinuousScheduler._plan_prefill``: with ``m`` leading ids shared
    with the best earlier prompt and ``k`` the fewest windows of ``S`` for
    which ``n - k S <= m``, the row copies ``n - k S`` positions (none
    when that is not positive) and runs its last ``k`` windows."""
    S = EXTEND_WINDOW
    reused = []
    for i, ids in enumerate(token_rows):
        n = len(ids)
        m = max((lcp_len(ids, prev) for prev in token_rows[:i]), default=0)
        k = max(1, -(-(n - m) // S))
        reused.append(max(n - k * S, 0))
    return reused


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--phase", type=int, choices=(1, 3), default=1)
    ap.add_argument("--profiles", type=int, default=None,
                    help="profiles per demographic combo (default: config)")
    ap.add_argument("--variant", default="conformal",
                    choices=("conformal", "smart", "aggressive"),
                    help="phase-3 prompt variant")
    ap.add_argument("--strategy", default="demographic_parity")
    ap.add_argument("--data-dir", default=None)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the stats as JSON")
    args = ap.parse_args()

    config = default_config()
    data = load_movielens(args.data_dir or config.data_dir,
                          seed=config.random_seed)
    base = create_base_preferences(data, seed=config.random_seed)
    profiles = create_profile_grid(base, config, args.profiles)
    if args.phase == 1:
        prompts = [recommendation_prompt(p) for p in profiles]
    else:
        anonymize = args.variant in ("smart", "aggressive")
        prompts = [
            fairness_aware_prompt(
                recommendation_prompt(p, anonymize=anonymize),
                args.strategy if args.variant == "conformal"
                else "individual_fairness",
                aggressive=(args.variant == "aggressive"),
            )
            for p in profiles
        ]

    by_id = dict(zip((p.id for p in profiles), prompts))
    pair_stats = divergence_stats(
        [(by_id[a], by_id[b]) for a, b in profile_pairs(profiles)]
    )

    tok = ByteTokenizer(512)
    rows = [tok.encode(p) for p in prompts]
    reused = expected_reuse(rows)
    total = sum(len(r) for r in rows)
    hit = sum(reused)
    fracs = [m / len(r) for m, r in zip(reused, rows)]
    hist = Counter(int(f * 10) / 10 for f in fracs)

    stats = {
        "phase": args.phase,
        "num_prompts": len(prompts),
        "window": EXTEND_WINDOW,
        "total_tokens": total,
        "reused_tokens": hit,
        "expected_reuse_share": hit / total if total else 0.0,
        "pair_divergence": pair_stats,
        "reused_fraction_histogram": {
            f"{k:.1f}": hist[k] for k in sorted(hist)
        },
    }
    print(f"prompts: {len(prompts)}   window: {EXTEND_WINDOW}   "
          f"tokens: {total}")
    print(f"counterfactual pairs: {pair_stats['pairs']}   shared-prefix "
          f"fraction min/mean/max: {pair_stats['min_frac']:.3f} / "
          f"{pair_stats['mean_frac']:.3f} / {pair_stats['max_frac']:.3f}")
    print(f"expected prefill_tokens_reused / prefill_tokens (sweep order, "
          f"every earlier row resident): "
          f"{stats['expected_reuse_share']:.3f}")
    print("reused fraction histogram (per prompt):")
    n = len(prompts)
    for k in sorted(hist):
        bar = "#" * max(1, round(40 * hist[k] / n))
        print(f"  {k:>4.1f}-{k + 0.1:.1f}  {hist[k]:5d}  {bar}")
    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(stats, f, indent=2)
        print(f"wrote {args.json}")
    # The layout contract: pairs must share most of their bytes, or the
    # defining workload has nothing for admission to reuse.
    return 0 if (not pair_stats["pairs"]
                 or pair_stats["min_frac"] >= 0.5) else 1


if __name__ == "__main__":
    sys.exit(main())
