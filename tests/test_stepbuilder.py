"""Step-program builder parity harness (ISSUE 14 + the mesh axis).

The correctness contract for ``runtime/stepbuilder.py`` is that every
composition the builder emits — across the five axes it exposes — decodes
token-for-token what ``DecodeEngine.generate`` decodes for the same prompt
alone:

    {dense, latent cache} x {greedy, spec-verify} x {guards on, off}
                          x {fuse 1, 2, 4} x {tp 1, 2, 8}    (where legal)

The tp axis runs on REAL devices (conftest forces 8 virtual CPU devices):
a tp mesh shards params, the slot KV cache (kv-head axis), and the carried
logits (vocab), and every program lowers as one SPMD computation — parity
through slot recycling, a sharded NaN-guard containment, and a sharded
fused-window requeue is pinned below, plus the ``("tp", k)`` compile-key
element's tp=1 byte-identity and the ``@tp<k>`` telemetry label scheme.

Illegal cells are structural, not skipped-for-time: spec-verify is an
engine-path selection (the serving scheduler is greedy/sampled per-row),
latent heads over ``tp`` are refused, and fuse composes only with the
serving dispatch (the engine's whole generation is already one dispatch).

On top of the grid: recycled-slot, requeue-after-fault, and fleet-migration
parity for FUSED serving (the chunk boundary moved — the containment and
migration machinery must not care), the one compile-key scheme's pinned
layout, the fused-vs-unfused roofline byte oracle, fused telemetry
attribution (a fused program publishes under its own label), the
degradation ladder's fuse reset, and the CLI flag gates.
"""

import numpy as np
import pytest

from fairness_llm_tpu.config import (
    FleetConfig,
    IntegrityConfig,
    MeshConfig,
    ModelSettings,
    ResilienceConfig,
    ServingConfig,
    SpeculationConfig,
)
from fairness_llm_tpu.models.configs import get_model_config
from fairness_llm_tpu.runtime.engine import DecodeEngine
from fairness_llm_tpu.runtime.sampling import SamplerSettings
from fairness_llm_tpu.parallel import make_mesh
from fairness_llm_tpu.runtime.stepbuilder import (
    STEP_PROGRAMS,
    base_program,
    compile_key,
    program_label,
)
from fairness_llm_tpu.serving.fleet import ReplicaSet
from fairness_llm_tpu.serving.request import Request
from fairness_llm_tpu.serving.scheduler import ContinuousScheduler
from fairness_llm_tpu.telemetry import use_registry
from fairness_llm_tpu.telemetry.timeline import set_attribution, use_timeline
from fairness_llm_tpu.utils.failures import ScriptedFaultInjector


def greedy(m: int) -> ModelSettings:
    return ModelSettings(temperature=0.0, max_tokens=m)


# A near-duplicate family (a shared prefix for admission to copy) plus
# genuinely mixed-length odd prompts, enough of them that a 2-slot pool
# recycles every slot several times per serve.
PROMPTS = [
    "recommend movies for a user who likes drama and history",
    "recommend movies for a user who likes drama and comedy",
    "recommend movies for a user who likes drama and action",
    "the quick brown fox",
    "one two three one two three one",
    "zz zz zz",
]

M = 8  # tokens per request — enough to cross several chunk boundaries


def _scfg(fuse=1, slots=2, chunk=2):
    return ServingConfig(
        enabled=True, num_slots=slots, queue_capacity=64,
        max_prompt_len=192, max_new_tokens=32, decode_chunk=chunk,
        fuse_steps=fuse,
    )


@pytest.fixture(scope="module")
def engine():
    return DecodeEngine(get_model_config("tiny-test"), seed=0)


@pytest.fixture(scope="module")
def baseline(engine):
    """Per-prompt single-request engine reference — what every builder
    composition must reproduce token-for-token."""
    return {p: np.asarray(engine.generate([p], greedy(M)).tokens[0])
            for p in PROMPTS}


def _assert_parity(engine, baseline, requests, results):
    by_id = {r.id: r for r in results} if isinstance(results, dict) else None
    for req, res in zip(requests, results if by_id is None else
                        [by_id[q.id] for q in requests]):
        assert res.ok, (req.id, res.finish_reason, res.error)
        got = np.asarray(res.tokens)
        ref = baseline[req.prompt]
        n = len(got)
        assert n > 0 and np.array_equal(got, ref[:n]) \
            and np.all(ref[n:] == engine.tokenizer.pad_id), \
            (req.id, list(got), list(ref))


# -- the compile-key scheme ----------------------------------------------------


def test_compile_key_scheme_layout():
    """The pinned layout invariants: key[0] is the program (the speculation
    slot), the guard flag closes ``decode`` keys and sits mid-key on
    ``spec_decode`` (trailing pair = the speculation knobs), step keys
    carry (chunk, guard, fuse)."""
    s = SamplerSettings(temperature=0.0)
    k = compile_key("decode", batch=8, prompt_len=64, max_new=32, sampler=s,
                    prefix_len=0, guard=True)
    assert k[0] == "decode" and k[-1] is True
    k = compile_key("spec_decode", batch=8, prompt_len=64, max_new=32,
                    prefix_len=0, guard=False, ngram_max=3, draft_len=8)
    assert k[0] == "spec_decode" and k[-2:] == (3, 8) and k[5] is False
    assert compile_key("serve_step", chunk=8, guard=False) == \
        ("serve_step", 8, False, 1)
    assert compile_key("serve_step", chunk=4, guard=True, fuse=4) == \
        ("serve_step", 4, True, 4)
    assert compile_key("serve_prefill", nb=4, P=64, guard=False) == \
        ("serve_prefill", 4, 64, False)
    assert compile_key("prefix", prefix_len=128) == ("prefix", 128)
    for gone in ("warp_drive", "paged_step", "paged_prefill"):
        with pytest.raises(ValueError):
            compile_key(gone)


def test_program_label_fused_naming():
    assert program_label("serve_step", 1) == "serve_step"
    assert program_label("serve_step", 4) == "serve_step_fused"
    assert program_label("serve_step", 2) == "serve_step_fused"
    assert set(STEP_PROGRAMS) == {"serve_step", "serve_step_fused"}


def test_compile_key_mesh_element():
    """The mesh axis: tp=1 keys are BYTE-IDENTICAL to the pre-mesh scheme
    (no trailing element, nothing re-ordered — caches and committed
    compile-stats keys survive the upgrade unchanged), tp>1 appends one
    tagged ``("tp", k)`` element that can never collide with the
    positional int axes."""
    assert compile_key("serve_step", chunk=8, guard=False, tp=1) == \
        compile_key("serve_step", chunk=8, guard=False) == \
        ("serve_step", 8, False, 1)
    assert compile_key("serve_prefill", nb=4, P=64, guard=False, tp=1) == \
        ("serve_prefill", 4, 64, False)
    assert compile_key("serve_step", chunk=8, guard=False, tp=2) == \
        ("serve_step", 8, False, 1, ("tp", 2))
    assert compile_key("serve_prefill", nb=4, P=64, guard=True, tp=8) == \
        ("serve_prefill", 4, 64, True, ("tp", 8))
    assert compile_key("serve_step", chunk=8, guard=True, fuse=4, tp=8) == \
        ("serve_step", 8, True, 4, ("tp", 8))
    # Disjoint across the whole (chunk, guard, fuse, tp) product.
    keys = {compile_key("serve_step", chunk=c, guard=g, fuse=f, tp=t)
            for c in (4, 8) for g in (False, True)
            for f in (1, 4) for t in (1, 2, 8)}
    assert len(keys) == 24
    # A tp=2 fuse=1 key can't alias a tp=1 fuse=2 key (or any other
    # positional coincidence): the tag makes the element self-describing.
    assert compile_key("serve_step", chunk=2, guard=False, tp=2) != \
        compile_key("serve_step", chunk=2, guard=False, fuse=2)


def test_program_label_mesh_suffix():
    """tp>1 programs publish under ``<base>[_fused]@tp<k>`` so sharded and
    single-device measurements never mix in one telemetry series; tp=1
    labels are byte-identical to the pre-mesh names. ``base_program``
    strips the suffix for structural (``*_fused``) checks."""
    assert program_label("serve_step", 1, tp=1) == "serve_step"
    assert program_label("serve_step", 1, tp=2) == "serve_step@tp2"
    assert program_label("serve_step", 4, tp=8) == "serve_step_fused@tp8"
    assert program_label("serve_prefill", tp=2) == "serve_prefill@tp2"
    assert base_program("serve_step_fused@tp8") == "serve_step_fused"
    assert base_program("serve_step") == "serve_step"
    assert base_program("serve_step@tp2").endswith("_fused") is False
    assert base_program("serve_step_fused@tp2").endswith("_fused")


def test_step_keys_disjoint_across_fuse_and_chunk(engine):
    """A fused program can never reuse (or be reused by) the per-chunk
    program: the fuse factor is a compile-key axis, like the mutable
    decode_chunk the degradation ladder halves."""
    keys = {compile_key("serve_step", chunk=c, guard=g, fuse=f)
            for c in (4, 8) for g in (False, True) for f in (1, 2, 4)}
    assert len(keys) == 12


# -- the parity grid -----------------------------------------------------------


@pytest.fixture(scope="module")
def latent():
    """The latent-cache toy (``tests/toys.py``) with PROMPTS' lengths as
    ids, and what the engine alone decodes for each: float32 at the
    highest matmul precision, so that two programs part at near-ties only."""
    import jax
    from toys import KINDS, IdPrompts, alone, toy_engine

    with jax.default_matmul_precision("highest"):
        engine, cut = toy_engine(KINDS["latent"], seed=14)
        prompts = IdPrompts(engine, cut, 14, greedy(M), tag="lat")
        stem = prompts.draw(40)
        ids = [np.concatenate([stem, prompts.draw(n)]) for n in (15, 14, 16)]
        ids += [prompts.draw(n) for n in (19, 31, 8)]
        reqs = [prompts.request(x) for x in ids]
        solo = {r.id: alone(engine, r.id, greedy(M), M) for r in reqs}
        yield engine, reqs, dict(zip((r.id for r in reqs), ids)), solo


@pytest.mark.parametrize("kind", ["dense", "latent"])
@pytest.mark.parametrize("guard", [False, True], ids=["plain", "guarded"])
@pytest.mark.parametrize("fuse", [1, 2, 4])
def test_serving_grid_parity(engine, baseline, latent, kind, guard, fuse):
    """{k and v per head, one latent a token} x {guards on, off} x {fuse 1,
    2, 4}, greedy selection: 6 mixed requests over 2 slots (every slot
    recycles), each token-identical to the engine alone. The fused cells:
    per-row caps/EOS stops advance in-program, so folding k chunks into one
    dispatch must not move a single token."""
    from toys import assert_same_tokens

    if kind == "latent":
        engine, reqs, ids, solo = latent
    else:
        reqs = [Request(id=f"g{i}", prompt=p, settings=greedy(M))
                for i, p in enumerate(PROMPTS)]
    engine.numerics_guards = guard
    try:
        sched = ContinuousScheduler(engine, _scfg(fuse=fuse),
                                    settings=greedy(M))
        results = sched.serve(reqs)
        if kind == "latent":
            for req, res in zip(reqs, results):
                assert res.ok, (req.id, res.finish_reason, res.error)
                assert_same_tokens(engine, req.id, ids[req.id], res.tokens,
                                   solo[req.id][:len(res.tokens)])
        else:
            _assert_parity(engine, baseline, reqs, results)
        # The dispatched program compiled under the unified key.
        assert compile_key("serve_step", chunk=2, guard=guard, fuse=fuse) \
            in sched._compiled
    finally:
        engine.numerics_guards = False


@pytest.mark.parametrize("guard", [False, True], ids=["plain", "guarded"])
def test_spec_verify_composition_parity(engine, guard):
    """The spec-verify selection (engine path): the builder's draft-and-
    verify composition emits exactly the plain greedy composition's
    tokens, guards on or off."""
    spec = SpeculationConfig(enabled=True, draft_len=4, ngram_max=3)
    engine.numerics_guards = guard
    try:
        prompts = PROMPTS[:3]
        plain = engine.generate(prompts, greedy(16))
        spec_out = engine.generate(prompts, greedy(16), speculation=spec)
        np.testing.assert_array_equal(plain.tokens, spec_out.tokens)
        assert "speculation" in spec_out.stats
    finally:
        engine.numerics_guards = False


def test_fused_requeue_parity(engine, baseline):
    """A decode fault inside a FUSED window discards the whole dispatch
    and requeues every rider once — survivors re-decode token-identical
    (the containment contract is per dispatch, whatever its width)."""
    inj = ScriptedFaultInjector({("g1", "decode"): 1})
    sched = ContinuousScheduler(
        engine, _scfg(fuse=4), settings=greedy(M), fault_injector=inj,
    )
    reqs = [Request(id=f"g{i}", prompt=p, settings=greedy(M))
            for i, p in enumerate(PROMPTS[:4])]
    results = sched.serve(reqs)
    _assert_parity(engine, baseline, reqs, results)
    assert results[1].retries == 1
    assert sched.last_stats.requeued == 1


def test_fused_numerics_guard_containment(engine, baseline):
    """Injected NaN inside a fused window: the guard flag rides the fused
    carry, the whole dispatch is discarded as a NumericsFault at the
    dispatch boundary, and the requeued rider still decodes to parity —
    the chaos drill's fused fault case in miniature."""
    engine.numerics_guards = True
    try:
        inj = ScriptedFaultInjector({}, corruptions={("g0", "decode"): 1})
        sched = ContinuousScheduler(
            engine, _scfg(fuse=4), settings=greedy(M), fault_injector=inj,
            resilience=ResilienceConfig(enabled=True),
        )
        reqs = [Request(id=f"g{i}", prompt=p, settings=greedy(M))
                for i, p in enumerate(PROMPTS[:4])]
        with use_registry() as reg:
            results = sched.serve(reqs)
            m = reg.peek("faults_total", component="serving",
                         kind="numerics", stage="decode")
            assert m is not None and m.value >= 1
        _assert_parity(engine, baseline, reqs, results)
    finally:
        engine.numerics_guards = False


def test_fused_fleet_migration_parity(engine, baseline):
    """Fleet failover with FUSED replicas: kill r1 mid-sweep — zero lost,
    migrated survivors token-identical through r0's own fused dispatch."""
    # Crash on the FIRST health poll: a fused fleet finishes the sweep in
    # so few loop iterations that a later-scheduled crash would miss it.
    inj = ScriptedFaultInjector(replica_crashes={"r1": 1})
    fleet = ReplicaSet(
        engine, _scfg(fuse=4), settings=greedy(M),
        fleet=FleetConfig(replicas=2, fence_cooldown_s=0.02),
        resilience=ResilienceConfig(enabled=True, breaker_threshold=1,
                                    breaker_cooldown_s=0.01),
        integrity=IntegrityConfig(canary_max_tokens=8),
        fault_injector=inj,
    )
    reqs = [Request(id=f"g{i}", prompt=p, settings=greedy(M))
            for i, p in enumerate(PROMPTS)]
    results = fleet.serve(reqs)
    _assert_parity(engine, baseline, reqs, results)
    r0, r1 = fleet.replicas
    assert r1.fences == 1 and r0.fences == 0


def test_watchdog_budget_scales_with_fuse():
    """A fused dispatch legitimately runs k chunks of wall: a hang budget
    tuned for one chunk must not classify every healthy fused dispatch as
    a hang (the scheduler passes budget_scale=fuse_steps), while a stall
    past the SCALED budget still raises."""
    from fairness_llm_tpu.resilience.watchdog import StepWatchdog
    from fairness_llm_tpu.utils.failures import HangFault

    with use_registry():
        wd = StepWatchdog(0.1)
        # 5 chunks of wall under fuse=8: healthy, within the scaled budget.
        assert wd.observe("decode", elapsed=0.5, budget_scale=8) == 0.5
        # The same wall with no scaling (fuse=1) is a hang.
        with pytest.raises(HangFault):
            wd.observe("decode", elapsed=0.5)
        # A stall past even the scaled budget still classifies.
        with pytest.raises(HangFault):
            wd.observe("decode", elapsed=1.0, budget_scale=8)


def test_degradation_rung2_resets_fuse(engine):
    """Rung 2's smaller-compiled-steps posture: the fused dispatch drops
    to 1 alongside the halved chunk, and both restore on retreat."""

    class _Ladder:
        level = 2
        rung = "reduced_footprint"

    class _Board:
        ladder = _Ladder()

    sched = ContinuousScheduler(engine, _scfg(fuse=4, chunk=8),
                                settings=greedy(M))
    sched.breakers = _Board()
    sched._apply_degradation()
    assert sched.fuse_steps == 1 and sched.decode_chunk == 4
    _Ladder.level = 0
    sched._apply_degradation()
    assert sched.fuse_steps == 4 and sched.decode_chunk == 8
    engine.restore_speculation()


# -- fused telemetry attribution ----------------------------------------------


def test_fused_program_publishes_own_telemetry(engine):
    """A fused program appearing in compiles_total publishes its OWN cost
    ledger, roofline gauges, and host-gap accumulator under the
    ``serve_step_fused`` label — what ``validate_telemetry``'s extended
    --require-costmodel/--require-profile gates hold it to."""
    prev = set_attribution(True)
    try:
        with use_registry() as reg, use_timeline():
            sched = ContinuousScheduler(engine, _scfg(fuse=2),
                                        settings=greedy(M))
            reqs = [Request(id=f"t{i}", prompt=p, settings=greedy(M))
                    for i, p in enumerate(PROMPTS)]
            results = sched.serve(reqs)
            assert all(r.ok for r in results)

            def rows(name):
                return [m for m in reg.instruments()
                        if m.name == name
                        and m.labels.get("program") == "serve_step_fused"]

            assert any(m.value >= 1 for m in rows("compiles_total"))
            assert rows("cost_ledger_bytes"), \
                "fused program must publish its own ledger"
            assert rows("achieved_hbm_gbps"), \
                "fused program must publish its own roofline gauges"
            gaps = rows("cost_host_gap_s_total")
            assert gaps and gaps[0].value > 0, \
                "fused dispatches must accumulate a measured host gap"
    finally:
        set_attribution(prev)


# -- CLI flag gates ------------------------------------------------------------


def test_cli_fuse_steps_validation():
    from fairness_llm_tpu.cli.main import main

    base = ["--phase", "1", "--quick", "--model", "simulated", "--no-save"]
    with pytest.raises(SystemExit, match="require --continuous"):
        main(base + ["--fuse-steps", "4"])
    with pytest.raises(SystemExit, match="must be >= 1"):
        main(base + ["--continuous", "--fuse-steps", "0"])
    with pytest.raises(SystemExit, match="cannot combine with --speculate"):
        main(base + ["--continuous", "--speculate", "--fuse-steps", "4"])


def test_serving_config_fuse_default_is_identity():
    """fuse_steps=1 is the byte-identical default: same compile key shape,
    same program label, no fused telemetry names anywhere."""
    assert ServingConfig().fuse_steps == 1
    assert program_label("serve_step", ServingConfig().fuse_steps) == \
        "serve_step"


# -- the mesh axis: real-mesh tensor-parallel serving --------------------------


@pytest.fixture(scope="module")
def tp2_engine():
    """tiny-test over a REAL 2-device tp mesh (conftest forces 8 virtual
    CPU devices): params sharded by the parallel/ rules, programs lowered
    SPMD with XLA-inserted collectives."""
    return DecodeEngine(get_model_config("tiny-test"), seed=0,
                        mesh=make_mesh(MeshConfig(tp=2)))


def _tp_scfg(tp, fuse=1):
    return ServingConfig(
        enabled=True, num_slots=2, queue_capacity=64,
        max_prompt_len=192, max_new_tokens=32, decode_chunk=2,
        fuse_steps=fuse, tp=tp,
    )


@pytest.mark.parametrize("fuse", [1, 4])
def test_tp2_serving_grid_parity(engine, baseline, tp2_engine, fuse):
    """{fuse 1, 4} at tp=2 (dense cache; latent heads over tp are refused):
    6 mixed requests over 2 slots (every slot recycles) decode token-for-token what the
    SINGLE-DEVICE engine decodes — sharding the cache on kv heads and the
    matmuls on the model axis must move zero tokens. The compiled key
    carries the ("tp", 2) element and the program the @tp2 label."""
    sched = ContinuousScheduler(
        tp2_engine, _tp_scfg(2, fuse=fuse), settings=greedy(M),
    )
    reqs = [Request(id=f"g{i}", prompt=p, settings=greedy(M))
            for i, p in enumerate(PROMPTS)]
    results = sched.serve(reqs)
    _assert_parity(engine, baseline, reqs, results)
    assert compile_key("serve_step", chunk=2, guard=False, fuse=fuse, tp=2) \
        in sched._compiled
    assert sched._step_program() == program_label("serve_step", fuse, tp=2)


def test_tp8_heads_replicate_parity(engine, baseline):
    """tp=8 over tiny-test (4 q heads / 2 kv heads): attention can't shard
    by heads, so it replicates while the ff (128) and vocab (512) axes DO
    shard — the mixed layout must still be token-exact. This is the
    degenerate-divisibility cell the sharding rules gate per-axis."""
    eng = DecodeEngine(get_model_config("tiny-test"), seed=0,
                       mesh=make_mesh(MeshConfig(tp=8)))
    sched = ContinuousScheduler(eng, _tp_scfg(8), settings=greedy(M))
    reqs = [Request(id=f"g{i}", prompt=p, settings=greedy(M))
            for i, p in enumerate(PROMPTS[:3])]
    results = sched.serve(reqs)
    _assert_parity(engine, baseline, reqs, results)


def test_tp2_numerics_guard_containment(engine, baseline, tp2_engine):
    """Injected NaN in a SHARDED fused window: the finite flag AND-reduces
    across shards inside the SPMD program, the dispatch is discarded as a
    NumericsFault, and the requeued rider decodes to parity — containment
    must not depend on where the poison lands in the mesh."""
    tp2_engine.numerics_guards = True
    try:
        inj = ScriptedFaultInjector({}, corruptions={("g0", "decode"): 1})
        sched = ContinuousScheduler(
            tp2_engine, _tp_scfg(2, fuse=4), settings=greedy(M),
            fault_injector=inj,
            resilience=ResilienceConfig(enabled=True),
        )
        reqs = [Request(id=f"g{i}", prompt=p, settings=greedy(M))
                for i, p in enumerate(PROMPTS[:4])]
        with use_registry() as reg:
            results = sched.serve(reqs)
            m = reg.peek("faults_total", component="serving",
                         kind="numerics", stage="decode")
            assert m is not None and m.value >= 1
        _assert_parity(engine, baseline, reqs, results)
    finally:
        tp2_engine.numerics_guards = False


def test_tp2_fused_requeue_parity(engine, baseline, tp2_engine):
    """A decode fault inside a SHARDED fused window: the whole dispatch
    discards, device state rebuilds RE-PLACED on the mesh (the donated
    sharded buffers were consumed), and every rider re-decodes
    token-identical."""
    inj = ScriptedFaultInjector({("g1", "decode"): 1})
    sched = ContinuousScheduler(
        tp2_engine, _tp_scfg(2, fuse=4), settings=greedy(M),
        fault_injector=inj,
    )
    reqs = [Request(id=f"g{i}", prompt=p, settings=greedy(M))
            for i, p in enumerate(PROMPTS[:4])]
    results = sched.serve(reqs)
    _assert_parity(engine, baseline, reqs, results)
    assert results[1].retries == 1
    assert sched.last_stats.requeued == 1


def test_tp2_fleet_migration_parity(engine, baseline, tp2_engine):
    """Fleet failover with SHARDED replicas: kill r1 mid-sweep — zero
    lost, migrated survivors token-identical through r0's own sharded
    dispatch (migration moves requests, never sharded device state)."""
    inj = ScriptedFaultInjector(replica_crashes={"r1": 1})
    fleet = ReplicaSet(
        tp2_engine, _tp_scfg(2), settings=greedy(M),
        fleet=FleetConfig(replicas=2, fence_cooldown_s=0.02),
        resilience=ResilienceConfig(enabled=True, breaker_threshold=1,
                                    breaker_cooldown_s=0.01),
        integrity=IntegrityConfig(canary_max_tokens=8),
        fault_injector=inj,
    )
    reqs = [Request(id=f"g{i}", prompt=p, settings=greedy(M))
            for i, p in enumerate(PROMPTS)]
    results = fleet.serve(reqs)
    _assert_parity(engine, baseline, reqs, results)
    r0, r1 = fleet.replicas
    assert r1.fences == 1 and r0.fences == 0


def test_tp2_sharded_telemetry_owns_mesh_labels(tp2_engine):
    """A sharded program publishes compile stats, cost ledger (including
    the nonzero ``collectives`` component — the tp all-reduce traffic)
    and roofline gauges under its OWN ``@tp2`` label, never polluting the
    single-device series — what validate_telemetry's extended
    --require-costmodel gate holds sharded runs to."""
    prev = set_attribution(True)
    try:
        with use_registry() as reg, use_timeline():
            sched = ContinuousScheduler(tp2_engine, _tp_scfg(2, fuse=2),
                                        settings=greedy(M))
            reqs = [Request(id=f"t{i}", prompt=p, settings=greedy(M))
                    for i, p in enumerate(PROMPTS[:4])]
            results = sched.serve(reqs)
            assert all(r.ok for r in results)
            label = "serve_step_fused@tp2"

            def rows(name, **extra):
                return [m for m in reg.instruments()
                        if m.name == name
                        and m.labels.get("program") == label
                        and all(m.labels.get(k) == v
                                for k, v in extra.items())]

            assert any(m.value >= 1 for m in rows("compiles_total"))
            coll = rows("cost_ledger_bytes", component="collectives")
            assert coll and sum(m.value for m in coll) > 0, \
                "sharded program must ledger its collectives traffic"
            assert rows("achieved_hbm_gbps"), \
                "sharded program must publish its own roofline gauges"
            # Nothing leaked into the unsharded label.
            assert not [m for m in reg.instruments()
                        if m.name == "cost_ledger_bytes"
                        and m.labels.get("program") == "serve_step_fused"]
    finally:
        set_attribution(prev)


def test_scheduler_rejects_dp_mesh_and_tp_mismatch(engine):
    """dp/sp meshes stay rejected at construction; a ServingConfig.tp that
    contradicts the engine's actual mesh fails loudly instead of silently
    serving single-device numbers under a mesh label."""
    dp_engine = DecodeEngine(get_model_config("tiny-test"), seed=0,
                             mesh=make_mesh(MeshConfig(dp=2)))
    with pytest.raises(ValueError, match="tp-only"):
        ContinuousScheduler(dp_engine, _scfg())
    with pytest.raises(ValueError, match="matching tp mesh"):
        ContinuousScheduler(engine, _tp_scfg(2))


def test_cli_tp_validation():
    """--tp follows the --fuse-steps parse-time discipline: every invalid
    combination dies in argparse/config_from_args with the flag named."""
    from fairness_llm_tpu.cli.main import main

    base = ["--phase", "1", "--quick", "--model", "simulated", "--no-save"]
    with pytest.raises(SystemExit, match="require --continuous"):
        main(base + ["--tp", "2"])
    with pytest.raises(SystemExit, match="must be >= 1"):
        main(base + ["--continuous", "--tp", "0"])
    with pytest.raises(SystemExit, match="cannot combine with --mesh"):
        main(base + ["--continuous", "--tp", "2", "--mesh", "dp=2"])
    # Head-count divisibility, checked against the model's config (the
    # conftest harness has 8 virtual devices, so the device gate passes
    # and the head gate must fire on its own).
    with pytest.raises(SystemExit, match="attention heads"):
        main(["--phase", "1", "--quick", "--model", "tiny-test", "--no-save",
              "--continuous", "--tp", "3"])
    # Device-count divisibility: 12 divides gpt2-small's heads but not the
    # harness's 8 devices.
    with pytest.raises(SystemExit, match="device count"):
        main(["--phase", "1", "--quick", "--model", "gpt2-small", "--no-save",
              "--continuous", "--tp", "12"])


def test_serving_config_tp_default_is_identity():
    """tp=1 is the byte-identical default: same compile keys, same
    labels, no mesh suffix anywhere, scheduler mesh-free."""
    assert ServingConfig().tp == 1
    assert program_label("serve_step", 1, tp=ServingConfig().tp) == \
        "serve_step"
    assert compile_key("serve_step", chunk=2, guard=False,
                       tp=ServingConfig().tp) == ("serve_step", 2, False, 1)
