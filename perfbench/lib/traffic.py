"""The one traffic generator. A mix is a data file of parameters; this turns
(mix, seed) into requests. Nothing here knows a cell by name.

Mix parameters (all cells): ``prompt_tokens`` {min, max}, ``output_tokens``,
``shared_prefix_share`` (the share of the shortest prompt that every request
of a group has in common, as the study's sweep prompts do), ``temperature``.
Closed mixes add ``pass_size``; open mixes add ``rate_per_s``, ``stratum_s``,
``lead_in_s`` and ``post_roll_s``.

What the seed decides and what it does not: the multiset of prompt lengths
and the number of arrivals in each stratum are fixed by the mix; the seed
permutes which request gets which length, places each arrival inside its
stratum, and draws the token ids. So two seeds give the same work in another
order.
"""

from __future__ import annotations

import dataclasses
import json
from typing import List, Optional

import numpy as np

# Ids below this are never drawn: 0..2 are pad/bos/eos in the configurations'
# vocabularies, and a prompt must not contain them.
FIRST_DRAWN_ID = 3


@dataclasses.dataclass
class RequestSpec:
    id: str
    ids: np.ndarray  # prompt token ids, int32
    max_new: int
    row_seed: int
    due_s: Optional[float] = None  # open loop: seconds from the schedule's 0

    def to_bytes(self) -> bytes:
        return (self.id.encode() + self.ids.tobytes()
                + np.asarray([self.max_new, self.row_seed], np.int64).tobytes()
                + np.asarray([-1.0 if self.due_s is None else self.due_s],
                             np.float64).tobytes())


def load_mix(path: str, overrides: Optional[dict] = None) -> dict:
    with open(path) as f:
        mix = json.load(f)
    mix.update(overrides or {})
    return mix


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  *stream])


def length_multiset(mix: dict, n: int) -> np.ndarray:
    """``n`` prompt lengths spread evenly over [min, max]: the same multiset
    for every seed."""
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    return np.rint(np.linspace(lo, hi, n)).astype(np.int64)


def _group(mix: dict, seed: int, stream: int, tag: str, vocab: int,
           lengths: np.ndarray) -> List[RequestSpec]:
    """One group of requests that share a prefix: ids drawn from the
    vocabulary, lengths as given (already permuted)."""
    rng = _rng(seed, stream, 1)
    prefix_len = int(round(mix.get("shared_prefix_share", 0.0)
                           * int(lengths.min())))
    prefix = rng.integers(FIRST_DRAWN_ID, vocab, size=prefix_len,
                          dtype=np.int64)
    out = []
    for i, n in enumerate(lengths):
        tail = rng.integers(FIRST_DRAWN_ID, vocab, size=int(n) - prefix_len,
                            dtype=np.int64)
        out.append(RequestSpec(
            id=f"{tag}-{i:05d}",
            ids=np.concatenate([prefix, tail]).astype(np.int32),
            max_new=int(mix["output_tokens"]),
            row_seed=int(rng.integers(0, 2**32)),
        ))
    return out


def closed_pass(mix: dict, seed: int, index: int, vocab: int,
                output_tokens: Optional[int] = None) -> List[RequestSpec]:
    """Pass ``index`` of a closed mix: ``pass_size`` fresh requests."""
    n = int(mix["pass_size"])
    lengths = _rng(seed, index, 0).permutation(length_multiset(mix, n))
    reqs = _group(mix, seed, index, f"s{seed}-p{index}", vocab, lengths)
    if output_tokens is not None:
        for r in reqs:
            r.max_new = output_tokens
    return reqs


def stratum_counts(rate_per_s: float, stratum_s: float, strata: int):
    """Arrivals per stratum: the same whole number in each, the fractional
    part carried to the next."""
    counts, acc = [], 0.0
    for _ in range(strata):
        acc += rate_per_s * stratum_s
        k = int(acc + 1e-9)
        counts.append(k)
        acc -= k
    return counts


def open_schedule(mix: dict, seed: int, seconds: float,
                  vocab: int) -> List[RequestSpec]:
    """An open-loop schedule over lead-in + window + post-roll, stratified.
    ``due_s`` counts from the start of the lead-in; the measured window is
    [lead_in_s, lead_in_s + seconds)."""
    stratum = float(mix.get("stratum_s", 1.0))
    total = float(mix["lead_in_s"]) + seconds + float(mix["post_roll_s"])
    strata = int(np.ceil(total / stratum))
    counts = stratum_counts(float(mix["rate_per_s"]), stratum, strata)
    n = int(sum(counts))
    rng = _rng(seed, 0, 0)
    lengths = rng.permutation(length_multiset(mix, n))
    due = np.concatenate([
        np.sort(k * stratum + rng.uniform(0.0, stratum, size=c))
        for k, c in enumerate(counts)
    ]) if n else np.zeros((0,))
    # Requests of one stratum group share a prefix, as the sweep's do.
    group = int(mix.get("prefix_group", 45))
    reqs: List[RequestSpec] = []
    for g0 in range(0, n, group):
        part = _group(mix, seed, 1 + g0 // group, f"s{seed}-g{g0 // group}",
                      vocab,
                      lengths[g0:g0 + group])
        reqs.extend(part)
    for r, d in zip(reqs, due):
        r.due_s = float(d)
    return reqs
