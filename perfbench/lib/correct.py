"""What decides ``correct``: a sample of the requests the window finished,
drawn from the seed with the longest in it, is run once through the plain
reference (teacher-forced: prompt + served tokens), and each served token's
reference logit is compared with the reference's best at that position.

Numbers compared, each with its limit (limits come from the cell's file):

- ``widest_gap``: the largest amount by which a served token's logit lies
  below the reference's best logit, over every served token of the sample.
  Greedy tokens only: a mix with a temperature above 0 is refused.
- ``length_faults``: sampled requests whose token count is neither the cap nor
  ended by the end-of-sequence id (limit 0, exact).
- ``unanswered``: requests due in the window that never came back or came back
  failed (limit 0, exact).
"""

from __future__ import annotations

import sys
from typing import Dict, Sequence

import numpy as np

from . import reference


class Served:
    """One finished request as the timed path returned it."""

    def __init__(self, spec, tokens: np.ndarray, ok: bool):
        self.spec = spec
        self.tokens = np.asarray(tokens, np.int64)
        self.ok = ok


def draw_sample(served: Sequence[Served], seed: int, count: int):
    """``count`` finished requests drawn from the seed, the longest (prompt
    plus served tokens) always among them."""
    done = [s for s in served if s.ok and len(s.tokens) > 0]
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: (len(done[i].spec.ids) + len(done[i].tokens),
                                 done[i].spec.id))
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 77])
    rest = [i for i in range(len(done)) if i != longest]
    pick = list(rng.permutation(rest)[: max(0, count - 1)])
    return [done[i] for i in [longest] + sorted(pick)]


def _teacher_forced(sample: Sequence[Served], multiple: int = 128):
    """Right-padded [N, S] token matrix of prompt + served[:-1], and the
    positions [N, T] whose logits predict each served token (pad positions
    repeat the last real one and are masked by ``counts``)."""
    t_max = max(len(s.tokens) for s in sample)
    s_max = max(len(s.spec.ids) + len(s.tokens) - 1 for s in sample)
    s_max = -(-s_max // multiple) * multiple
    tokens = np.zeros((len(sample), s_max), np.int32)
    take = np.zeros((len(sample), t_max), np.int32)
    counts = np.zeros((len(sample),), np.int64)
    for r, s in enumerate(sample):
        seq = np.concatenate([s.spec.ids, s.tokens[:-1]])
        tokens[r, : len(seq)] = seq
        n = len(s.tokens)
        first = len(s.spec.ids) - 1
        take[r, :n] = first + np.arange(n)
        take[r, n:] = first + n - 1
        counts[r] = n
    return tokens, take, counts


def gaps(cfg: dict, seed: int, sample: Sequence[Served],
         control_formats: Sequence[str] = ()) -> Dict[str, float]:
    """The served tokens' widest gap under the reference; for each of
    ``control_formats`` also the widest gap of the tokens that the lower
    precision puts first at the same positions (``control_<format>``)."""
    tokens, take, counts = _teacher_forced(sample)
    ref = reference.logits_at(cfg, seed, tokens, take)
    best = ref.max(axis=-1)
    mask = np.arange(take.shape[1])[None, :] < counts[:, None]
    served = np.zeros(take.shape, np.int64)
    for r, s in enumerate(sample):
        served[r, : len(s.tokens)] = s.tokens
    got = np.take_along_axis(ref, served[:, :, None], axis=-1)[:, :, 0]
    out = {"widest_gap": float(np.max(np.where(mask, best - got, 0.0))),
           "served_tokens": int(mask.sum())}
    for fmt in control_formats:
        low = reference.logits_at(cfg, seed, tokens, take, act_format=fmt)
        first = low.argmax(axis=-1)
        got = np.take_along_axis(ref, first[:, :, None], axis=-1)[:, :, 0]
        out[f"control_{fmt}"] = float(
            np.max(np.where(mask, best - got, 0.0)))
    return out


def length_faults(sample: Sequence[Served], eos_id: int) -> int:
    bad = 0
    for s in sample:
        n = len(s.tokens)
        ended = n > 0 and s.tokens[-1] == eos_id
        early_eos = eos_id in s.tokens[:-1]
        if early_eos or not (n == s.spec.max_new or (ended and n < s.spec.max_new)):
            bad += 1
    return bad


def judge(readings: Dict[str, float], limits: Dict[str, float]):
    """(correct, rows) where rows are (name, value, limit) for every number
    compared. A number with no limit in the cell's file is an error."""
    rows, ok = [], True
    for name in ("widest_gap", "length_faults", "unanswered"):
        value, limit = readings[name], limits[name]
        rows.append((name, value, limit))
        ok = ok and np.isfinite(value) and value <= limit
    return bool(ok), rows


def report(rows, stream=None) -> None:
    stream = stream or sys.stderr
    print("compared: " + "; ".join(
        f"{n}={v:.6g} limit={l:.6g}" for n, v, l in rows), file=stream)
