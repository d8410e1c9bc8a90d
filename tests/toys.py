"""The benchmark families' toys as serving engines, for the tests that ask
one question of both kinds of cache the program has: ``dense_gqa`` (k and v
per KV head) from Mistral's file and ``latent_moe`` (one latent a token)
from A.X-K1's. Float32 on int8 weights made from a seed, prompts as token
ids the tokenizer hands back by name."""

import contextlib
import dataclasses
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from fairness_llm_tpu.config import ModelSettings  # noqa: E402
from fairness_llm_tpu.runtime.engine import DecodeEngine  # noqa: E402
from fairness_llm_tpu.runtime.stepbuilder import extend_window  # noqa: E402
from fairness_llm_tpu.serving import Request  # noqa: E402
from fairness_llm_tpu.telemetry.memory import (  # noqa: E402
    count_pool_copies,
    count_rolled_rows,
)
from perfbench import families  # noqa: E402
from perfbench.lib import modelcfg  # noqa: E402
from perfbench.lib import weights as W  # noqa: E402
from perfbench.lib.sut import IdTokenizer  # noqa: E402

#: fixture ids -> the configuration whose rehearsal cut is the toy
KINDS = {"dense": "mistral-7b-int8", "latent": "ax-k1-int8"}
NEAR_TIE = 1e-4


def toy_engine(config: str, seed: int, **overrides):
    """(engine, cut) for one family's toy, float32, ids as prompts.
    ``overrides`` replace keys of the cut (a wider window, say)."""
    cut = modelcfg.rehearsal_cut(modelcfg.load_config(
        os.path.join(ROOT, "perfbench", "configs", config + ".json")))
    cut["eos_token_id"] = 1  # never drawn; a row may still end on it
    cut.update(overrides)
    family = families.of(cut)
    make_layer, make_globals = W.jitted_makers(cut)
    words = W.seed_words(seed)
    tree = family.program_tree(cut, make_globals(words), [
        make_layer(words, i) for i in range(cut["num_hidden_layers"])])
    mc = dataclasses.replace(family.model_config(cut, "toy"), dtype="float32")
    tok = IdTokenizer(cut["vocab_size"], cut["pad_token_id"],
                      cut["eos_token_id"])
    return DecodeEngine(mc, params=tree, tokenizer=tok, seed=0), cut


class IdPrompts:
    """Prompts as ids the tokenizer hands back by name."""

    def __init__(self, engine, cut, seed, settings: ModelSettings,
                 tag: str = "r"):
        self.tok, self.vocab = engine.tokenizer, cut["vocab_size"]
        self.rng = np.random.default_rng(seed)
        self.settings, self.tag, self.count = settings, tag, 0

    def draw(self, n):
        return self.rng.integers(3, self.vocab, size=n).astype(np.int32)

    def request(self, ids, settings: ModelSettings = None):
        name = f"{self.tag}{self.count}"
        self.count += 1
        self.tok.register(name, np.asarray(ids, np.int32))
        return Request(prompt=name, id=name,
                       settings=settings or self.settings)


def whole_prompt_logits(engine, ids):
    """Next-token logits of ONE forward over the whole prompt, no cache."""
    ids = jnp.asarray(ids, jnp.int32)[None]
    pos = jnp.arange(ids.shape[1], dtype=jnp.int32)[None]
    logits, _ = jax.jit(
        lambda p, t, q: engine.model.apply({"params": p}, t, q)
    )(engine.params, ids, pos)
    return np.asarray(logits[0, -1])


def assert_same_tokens(engine, what, ids, served, other):
    """Two programs decoded the same tokens from ``ids``; where they part,
    the whole-sequence logits must call the step a near-tie."""
    served, other = np.asarray(served), np.asarray(other)
    assert len(served) == len(other), (what, served, other)
    for t in range(len(served)):
        if served[t] != other[t]:
            ref = whole_prompt_logits(
                engine, np.concatenate([ids, served[:t]]))
            assert abs(ref[served[t]] - ref[other[t]]) < NEAR_TIE, (
                f"{what} token {t}: {served[t]} against {other[t]}, and "
                "the reference's logits are not a tie")
            break


def alone(engine, name, settings: ModelSettings, n: int):
    """The first ``n`` tokens ``DecodeEngine`` alone decodes for the
    registered prompt ``name``."""
    return np.asarray(engine.generate([name], settings).tokens[0])[:n]


def prefill_hlo(sched, nb: int, P: int, guard: bool = False,
                copies: bool = True) -> str:
    """The scheduler's own ``serve_prefill`` of bucket ``(nb, P)`` (of a
    batch that copies or not: ``stepbuilder.extend_window``) compiled for
    its pool, as text."""
    fn = sched._prefill_fn(nb, P, guard, copies)
    S = extend_window(sched.engine.config, P, copies, sched._rings)
    row = jnp.zeros((nb,), jnp.int32)
    return fn._jit.lower(
        sched.engine.params, sched._cache, sched._prev_logits,
        jnp.zeros((nb, S), jnp.int32), jnp.zeros((nb, S), bool),
        row, row, row, row, row, jnp.zeros((), jnp.int32),
    ).compile().as_text()


def rolled_rows(sched, nb: int, P: int, hlo: str) -> int:
    """``count_rolled_rows`` of a compiled ``serve_prefill`` of bucket
    ``(nb, P)`` over the scheduler's pool."""
    return count_rolled_rows(hlo, nb, P, sched._cache,
                             {sched.cache_len, *sched._retained})


def prefill_aliasing(sched, nb: int, P: int, guard: bool = False,
                     copies: bool = True, hlo: str = None):
    """The scheduler's own ``serve_prefill`` of bucket ``(nb, P)`` (of a
    batch that copies or not: ``stepbuilder.extend_window``) compiled for
    its pool (or ``hlo``, that program's text): (the parameters the module
    aliases to an output, the parameters that hold the pool and the carried
    logits, the copies of a whole pool leaf it makes)."""
    hlo = hlo or prefill_hlo(sched, nb, P, guard, copies)
    header = hlo[:hlo.index("\n")]
    aliased = sorted(int(i) for i in re.findall(
        r"\((\d+), \{\}, (?:may|must)-alias\)", header))
    held = len(jax.tree.leaves(sched.engine.params))
    donated = len(jax.tree.leaves((sched._cache, sched._prev_logits)))
    return aliased, list(range(held, held + donated)), \
        count_pool_copies(hlo, sched.num_slots,
                          {sched.cache_len, *sched._retained})


@contextlib.contextmanager
def prefill_fault(sched, at: int, how: str = "unlaunched"):
    """The ``at``-th ``serve_prefill`` call from here on fails. ``how``:
    ``unlaunched`` raises before the compiled call is made (what a trace or
    compile error does: the inputs live on); ``launched`` raises after it
    returned (the donated inputs are consumed); ``flag_down`` lets the call
    through and hands back its finite flag as False (a guarded program).
    Yields the list that counts the calls."""
    inner, calls = sched._run_compiled, []

    def failing(fn, *args):
        if getattr(fn, "program", "") != "serve_prefill":
            return inner(fn, *args)
        calls.append(1)
        hit = len(calls) == at
        if hit and how == "unlaunched":
            raise RuntimeError("scripted device fault")
        out = inner(fn, *args)
        if hit and how == "launched":
            raise RuntimeError("scripted device fault after launch")
        if hit and how == "flag_down":
            return out[:2] + (jnp.zeros((), bool),)
        return out

    sched._run_compiled = failing
    try:
        yield calls
    finally:
        sched._run_compiled = inner
