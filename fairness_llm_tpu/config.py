"""Configuration for the fairness_llm_tpu framework.

The reference keeps its configuration in a gitignored ``src/config.py`` whose schema
had to be reconstructed from call sites (SURVEY.md Appendix A; e.g. reference
``main.py:49-52``, ``phase1_bias_detection.py:99,186-187,280``). This module ships a
real, checked-in equivalent — extended with the TPU-specific knobs (mesh shape, model
selection, decode settings) that the reference, being a remote-API pipeline, never
needed.

Everything is a frozen dataclass so configs can be passed through jit boundaries as
static arguments and hashed for compilation caching.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelSettings:
    """Per-model decode settings (reference ``config.MODELS[name]``,
    used at ``phase1_bias_detection.py:186-187``)."""

    temperature: float = 0.7
    max_tokens: int = 500
    top_p: float = 1.0
    top_k: int = 0  # 0 = disabled


@dataclasses.dataclass(frozen=True)
class SpeculationConfig:
    """Prompt-lookup speculative decoding knobs (``runtime/speculative.py``).

    The phase-1/3 sweeps emit ranked lists of titles copied verbatim from the
    candidate list already in the prompt — the ideal regime for draft-free
    n-gram speculation: draft ``draft_len`` tokens by matching the last
    ``ngram_max`` generated tokens against the prompt + generated suffix, then
    verify all of them in ONE forward pass (decode is memory-bound, so the
    extra verify positions are nearly free). Greedy-only: with temperature>0
    the engine silently uses the plain sampled decode path (see
    ``runtime/sampling.py``). Frozen/hashable so it can sit inside the
    engine's compile keys — toggling it can never reuse a stale program.
    """

    enabled: bool = False
    ngram_max: int = 3  # longest suffix n-gram tried first (falls back to 1)
    draft_len: int = 8  # drafted tokens verified per step (k; step width k+1)


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Continuous-batching server knobs (``serving/``).

    The static engine pads every row of a ``generate`` call to the longest
    prompt and holds the whole batch until the last row drains; the serving
    subsystem instead runs a fixed pool of ``num_slots`` KV slots, evicts a
    row the step it finishes, and backfills the freed slot from a bounded
    admission queue — so a mixed-length workload decodes at per-request cost
    instead of per-chunk-maximum cost. Greedy decode through the server is
    token-for-token identical to ``DecodeEngine.generate`` alone for
    prompts within ``max_prompt_len`` (pinned in tests/test_serving.py;
    longer prompts left-truncate to the serving budget, with a warning).
    """

    enabled: bool = False
    num_slots: int = 8  # concurrent KV slots = decode-step batch rows
    queue_capacity: int = 128  # bounded admission queue (backpressure past this)
    max_prompt_len: int = 512  # per-request prompt budget (left-truncated over)
    max_new_tokens: int = 256  # hard per-request decode cap (requests clamp to it)
    prefill_group: int = 8  # max admissions prefilled in one compiled forward
    # Decode steps per compiled scheduler call: larger chunks amortize
    # per-call dispatch/copy overhead, smaller chunks backfill freed slots
    # sooner (an evicted row's slot idles at most decode_chunk-1 steps).
    decode_chunk: int = 8
    # Fused multi-step dispatch (--fuse-steps, runtime/stepbuilder.py):
    # fold k decode chunks into ONE compiled dispatch — the step program
    # runs decode_chunk x fuse_steps steps before returning to the host,
    # so per-dispatch host work (eviction sweep, queue polls, telemetry,
    # the blocking device_get) amortizes 1/k per token. The token stream
    # is identical at any k (per-row caps/EOS stops advance in-program and
    # the loop early-exits once every live row finishes); the trade is
    # latency granularity — eviction/backfill, drain polls, breaker feeds,
    # and watchdog observes all move to the fused-dispatch boundary, and a
    # contained fault discards up to k chunks of work. Composition with
    # --speculate (whose verify window is already multi-token) is deferred
    # to the tree-speculation PR and refused at flag parse.
    fuse_steps: int = 1
    # Optional admission rate limit (RateLimiter.try_acquire at submit);
    # None = no quota. Exists for parity with the reference's API-era
    # limiter and for multi-tenant deployments.
    admission_per_minute: Optional[int] = None
    # Tensor-parallel serving (--tp, runtime/stepbuilder.py's mesh axis):
    # every compiled serving program lowers as ONE SPMD computation over a
    # tp-way mesh — params placed by parallel/sharding.py rules, the KV
    # cache sharded on KV heads, XLA GSPMD inserting the all-reduces.
    # The scheduler cross-checks this against the engine's actual mesh (a
    # tp=2 ServingConfig on a meshless engine fails loudly at construction
    # instead of silently serving single-device). tp=1 is byte-identical
    # to the pre-mesh scheduler: same compile keys, same telemetry labels.
    tp: int = 1


@dataclasses.dataclass(frozen=True)
class OverloadConfig:
    """Overload-control knobs (``serving/overload.py``).

    ``enabled`` switches the serving front door from one FIFO to the QoS
    model: per-class bounded sub-queues (``interactive`` / ``batch`` /
    ``probe``) with strict-priority-with-aging dequeue, per-class rate
    quotas, deadline-feasibility admission (a request that provably cannot
    meet its deadline is REJECTED with a retry-after hint instead of
    burning a prefill and expiring later), and an SLO-driven shed
    controller that walks a brownout ladder under sustained overload:

        0 healthy -> 1 shed batch admissions -> 2 also cap batch
        max_new_tokens -> 3 interactive-only

    Escalation reads the fast-window SLO burn rates (``telemetry/slo.py``)
    and the admission-queue depth; de-escalation requires
    ``healthy_window_s`` of sustained health per rung (hysteresis — a
    flapping signal cannot oscillate the ladder). With ``enabled=False``
    (the default) the serving path is byte-identical to before.
    """

    enabled: bool = False
    # Per-class sub-queue bounds (each also respects the overall
    # ServingConfig.queue_capacity). Probes are synthetic health traffic;
    # a handful queued is already a sign something is stuck.
    interactive_capacity: int = 64
    batch_capacity: int = 64
    probe_capacity: int = 8
    # Per-class admission quotas (RateLimiter.try_acquire at submit);
    # None = no per-class quota (the shared ServingConfig quota still
    # applies when set).
    interactive_per_minute: Optional[int] = None
    batch_per_minute: Optional[int] = None
    probe_per_minute: Optional[int] = None
    # Strict-priority dequeue, EXCEPT a lower-class request waiting this
    # long is promoted (oldest-first among promoted) — bounded starvation
    # for batch under a steady interactive stream. <= 0 disables aging
    # (pure strict priority).
    aging_s: float = 5.0
    # Deadline-feasibility admission: reject-with-retry-after when the
    # remaining deadline is below ``feasibility_safety`` x the estimated
    # earliest first token (queue wait + prefill from live telemetry).
    # The safety factor keeps the bound conservative — only provably
    # doomed requests shed; 0 disables the check.
    deadline_admission: bool = True
    feasibility_safety: float = 0.5
    # Shed-controller signals: escalate one rung per evaluation while the
    # queue depth has reached ``queue_frac_threshold`` of capacity within
    # the sampling window, OR — only while interactive traffic has been
    # seen within ``interactive_presence_s`` — the fast-window burn rate
    # (error_rate or ttft_p95) is at/over ``burn_threshold``. The presence
    # gate is what keeps a single-tenant batch sweep (whose own deep queue
    # legitimately burns the TTFT budget) from browning itself out when
    # there is no interactive tenant to protect.
    burn_threshold: float = 2.0
    interactive_presence_s: float = 60.0
    queue_frac_threshold: float = 0.9
    queue_window_s: float = 2.0  # depth-sample memory (self-decaying hwm)
    healthy_window_s: float = 5.0  # sustained health per de-escalation rung
    eval_interval_s: float = 0.25  # min seconds between controller steps
    # Rung 2: batch requests' max_new_tokens clamp (smaller answers under
    # brownout beat no answers; interactive budgets are never touched).
    batch_token_cap: int = 32
    retry_after_s: float = 1.0  # base retry-after hint for class sheds
    # Opt-in (> 0): engage the rung-2 batch-token clamp EARLY whenever the
    # memory ledger's measured HBM headroom fraction falls to/below this —
    # decode tokens are KV bytes, so shortening batch answers is the
    # cheapest lever against an approaching memory wall (ISSUE 18). 0
    # keeps the ladder purely load-driven (byte-identical to before).
    headroom_cap_frac: float = 0.0


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Data-parallel replica fleet knobs (``serving/fleet.py``).

    ``replicas`` > 1 puts a :class:`ReplicaSet` behind the serving backend:
    N independent engine replicas — each with its own KV slot pool,
    scheduler, BreakerBoard, watchdog, and rejoin canary — fed from one
    bounded admission queue by a health-aware router
    (``serving/router.py``). Replica-level fault containment is the point:
    a replica whose degradation ladder climbs past ``fence_ladder_level``
    (or whose stall probe fires, or that takes an injected
    replica_crash/replica_hang) is FENCED — drained through the journal
    path with zero grace, its unfinished requests re-routed to healthy
    replicas with their original ids/settings/row_seeds so survivors keep
    token-for-token greedy parity — and rejoins only after passing a
    canary warm-up probe once ``fence_cooldown_s`` elapses (half-open at
    fleet granularity, mirroring the per-stage breaker state machine).

    ``fence_cooldown_s`` is the EARLIEST rejoin probe; the probe decodes
    through the fenced replica's own breakers, so when those are still
    open inside their own ``breaker_cooldown_s`` the fleet defers the
    probe until they can half-open (probing earlier would block the
    single-threaded fleet loop against a refusing stage). The effective
    rejoin delay is therefore max(fence_cooldown_s, remaining breaker
    cooldown).
    """

    replicas: int = 1  # 1 = single engine, no fleet layer
    # Degradation level at which the router fences a replica (2 =
    # reduced_footprint: the replica has already shed speculation AND
    # halved its footprint — past that, migrating its work beats letting
    # it limp). 0 disables ladder-driven fencing (crash/hang/stall still
    # fence).
    fence_ladder_level: int = 2
    # Simultaneously-open stage breakers that fence regardless of ladder
    # level (2 = both prefill and decode dead).
    fence_open_breakers: int = 2
    fence_cooldown_s: float = 1.0  # fenced -> first rejoin-probe delay


@dataclasses.dataclass(frozen=True)
class AutoscaleConfig:
    """SLO-coupled elastic-fleet knobs (``serving/autoscaler.py``).

    ``enabled`` puts an :class:`Autoscaler` on the ``ReplicaSet``'s tick:
    replica membership becomes a RUNTIME control loop instead of a fixed
    ``--replicas N`` startup choice. The controller reads the signals the
    stack already exports — per-replica fast-window ``slo_burn_rate``
    gauges (telemetry/slo.py), the fleet ``overload_level`` rung
    (serving/overload.py), and fleet-held queue depth — and drives
    membership through the fence machinery:

    - **scale-up**: a hot signal sustained for ``up_window_s`` (and past
      ``cooldown_s`` since the last action) instantiates a STANDBY replica
      — its own scheduler/SlotPool/BreakerBoard over the same engine
      params — which is canary-gated through the fleet's rejoin probe
      before it takes any traffic (a replica that cannot decode the golden
      prompt never joins);
    - **scale-down**: every signal cold for ``down_window_s`` retires the
      lowest-load replica through the zero-grace
      ``request_drain``/journal-migration path, so its in-flight requests
      migrate to the survivors with original ids/settings/row_seeds
      (token-for-token parity — the same contract a fence keeps).

    Hysteresis: at most one membership change per ``cooldown_s``, each
    direction requiring its own sustained window — a flapping signal can
    never oscillate the fleet. ``min_replicas``/``max_replicas`` bound the
    fleet absolutely. See docs/SERVING.md §Elastic fleet & autoscaling.
    """

    enabled: bool = False
    min_replicas: int = 1
    max_replicas: int = 4
    # Scale-up signals: fast-window burn rate (error_rate or ttft_p95, the
    # hottest replica) at/over up_burn_threshold, fleet-held queue depth
    # at/over up_queue_frac of capacity, or the brownout ladder at/past
    # up_overload_level (0 disables that signal).
    up_burn_threshold: float = 2.0
    up_queue_frac: float = 0.8
    up_overload_level: int = 1
    # Opt-in (> 0): treat measured HBM headroom at/under this fraction of
    # the device limit as a hot signal (memory ledger, ISSUE 18) — more
    # replicas spread the KV pools across more devices' HBM. 0 disables.
    up_headroom_frac: float = 0.0
    up_window_s: float = 1.0  # sustained hot before a scale-up
    # Scale-down: burn under down_burn_threshold AND queue under
    # down_queue_frac AND per-replica slot load under down_load_frac,
    # sustained for down_window_s.
    down_burn_threshold: float = 0.5
    down_queue_frac: float = 0.1
    down_load_frac: float = 0.5
    down_window_s: float = 5.0
    cooldown_s: float = 2.0  # min seconds between membership changes
    eval_interval_s: float = 0.25  # min seconds between controller steps


@dataclasses.dataclass(frozen=True)
class RolloutConfig:
    """Zero-downtime rolling-upgrade knobs (``serving/rollout.py``).

    A :class:`RolloutController` drives a canary-gated wave upgrade over a
    :class:`ReplicaSet`: per wave it adds ONE standby replica at the target
    version (canary-gated through the fleet's rejoin probe — a v+1 replica
    that cannot decode its own golden prompt never takes traffic), walks a
    traffic fraction to the new version in ``traffic_steps`` increments
    (version-aware ``HealthRouter`` steering), watches the deployment gates
    for ``canary_window_s`` per step, then retires one old-version replica
    through the planned-exit path — repeating until the fleet is entirely
    on the new version. Requests carry **pinned-version affinity**: a
    request completes on the version that admitted it (migration targets
    the same version while one lives), so greedy token parity holds
    per-version mid-rollout.

    Any gate firing while new-version replicas exist triggers an
    **automatic rollback**: canary mismatch on the new version, a fairness
    alert or counterfactual pair divergence attributed to a new replica
    (``abort_on_fairness_alert``), fast-window SLO error burn at/over
    ``gate_burn_threshold`` on a new replica's label, manifest refusal of
    the incoming weights, or a watchdog/breaker fence of a new replica —
    the new replicas are re-fenced, their in-flight work migrates back,
    and a ``rollout`` incident bundle names the triggering gate. While a
    rollout is active the autoscaler is paused (one owner of replica
    membership at a time). See docs/SERVING.md §Rollouts.
    """

    enabled: bool = False
    # Gate-watch window per traffic step: how long the controller holds
    # each traffic fraction while watching the deployment gates before
    # advancing the wave.
    canary_window_s: float = 1.0
    # Traffic increments per wave: the new-version share walks from its
    # previous plateau to the next in this many equal steps.
    traffic_steps: int = 2
    # Fast-window slo_burn_rate on a new-version replica's label at/over
    # this triggers rollback (same scale as AutoscaleConfig thresholds).
    gate_burn_threshold: float = 2.0
    # Treat ANY fairness alert (and any counterfactual pair divergence
    # whose attribution names a new-version replica) during the gate
    # window as a rollback trigger — the FairnessMonitor as a deployment
    # gate.
    abort_on_fairness_alert: bool = True


@dataclasses.dataclass(frozen=True)
class ResilienceConfig:
    """Watchdog / circuit-breaker / graceful-drain knobs (``resilience/``).

    ``enabled`` arms the step watchdog and the per-stage breakers in the
    serving scheduler (and the engine's speculate breaker). In a fault-free
    run they change NOTHING but a few host-side timestamps — the watchdog
    only classifies steps slower than ``max_step_seconds``, and a breaker
    only acts after ``breaker_threshold`` consecutive faults — which is why
    the bench guard (docs/PERFORMANCE.md) can pin their overhead at noise.

    ``journal_dir`` turns on the crash-safe serving journal: accepted
    requests are ledgered to ``<dir>/journal.jsonl`` and a drained/preempted
    run's unfinished work is re-servable with ``resume-serving <dir>``.
    """

    enabled: bool = False
    # Watchdog: a compiled step slower than this is classified hung and
    # raised as a containable HangFault. 0 disables classification (the
    # step_wall_s histogram still records, so thresholds can be chosen
    # from real data first).
    max_step_seconds: float = 0.0
    breaker_threshold: int = 3  # consecutive faults per stage -> open
    breaker_cooldown_s: float = 5.0  # open -> half-open probe delay
    # Drain: how long live slots may keep decoding after SIGTERM/SIGINT
    # before being journaled as unfinished (preemption notice is ~30s on
    # most preemptible fleets; leave headroom for the snapshot write).
    drain_grace_s: float = 5.0
    journal_dir: Optional[str] = None
    journal_rotate_every: int = 256  # terminal records between compactions


@dataclasses.dataclass(frozen=True)
class IntegrityConfig:
    """Silent-corruption detection knobs (``integrity/``).

    ``numerics_guards`` folds an on-device finite check of the logits into
    every compiled prefill/decode/speculative program — one AND-reduced
    flag per chunk, no host sync per token; a tripped flag is contained as
    a ``NumericsFault`` (requeue-once / chunk-retry, breaker-visible). Off
    by default: guarded programs compile under their own keys and the
    token stream is identical either way, so flipping it is always safe.

    ``verify_manifests`` (default ON) checks the sha256 ``manifest.json``
    beside weight checkpoints at load when one exists — a corrupt shard is
    refused with an error naming the file. Artifacts without a manifest
    load as before.

    ``canary_every_n`` > 0 arms the serving canary: every N backend
    ``generate`` calls, a golden prompt decodes through the live scheduler
    and is compared token-for-token against a reference recorded from the
    static engine; a mismatch trips the decode breaker (and with it the
    degradation ladder). See docs/RESILIENCE.md §Integrity.
    """

    numerics_guards: bool = False
    verify_manifests: bool = True
    canary_every_n: int = 0  # 0 = canary off
    canary_max_tokens: int = 16


@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """Performance-attribution knobs (``telemetry/timeline|roofline|slo``).

    The attribution layer itself is always on (host-side bookkeeping; the
    bench ``profiling_overhead`` guard pins its cost at harness noise) —
    these fields control the on-disk trace export, the roofline reference,
    and the SLO objectives the burn-rate evaluator judges against.

    ``trace_out`` writes the device-step timeline as Chrome-trace JSON
    (open in Perfetto) at end of run. With ``telemetry_dir`` set,
    ``<telemetry_dir>/trace.json`` is ALWAYS written (the copy
    ``validate_telemetry --require-profile`` and ``telemetry-report
    --timeline`` read) — ``trace_out`` adds an extra copy at an explicit
    path, or enables the export without a telemetry dir.

    SLO semantics (see ``telemetry/slo.py``): "p95 TTFT <= slo_ttft_p95_s"
    (at most 5% of requests over), "p99 e2e <= slo_e2e_p99_s" (at most 1%
    over), error rate <= ``slo_error_rate``; burn rates are computed over a
    fast window, a slow window, and the whole run.
    """

    trace_out: Optional[str] = None
    # Measured achievable streaming bandwidth for achieved_over_achievable
    # (None = the device's published peak from roofline.DEVICE_PEAKS; a
    # device without one publishes no fraction).
    achievable_gbps: Optional[float] = None
    # Fairness observability (telemetry/fairness.py, CLI --fairness-obs):
    # phases register their profile grid + counterfactual pairs with the
    # fairness monitor, sweep requests carry group/attribute/pair_id tags,
    # and the streaming DP/IF/exposure gauges + serving-neutrality audit +
    # pair watch record live. Off by default: the monitor stays idle and
    # every hook is a dict miss. See docs/OBSERVABILITY.md §Fairness.
    fairness_obs: bool = False
    slo_ttft_p95_s: float = 2.0
    slo_e2e_p99_s: float = 30.0
    slo_error_rate: float = 0.01
    slo_fast_window_s: float = 60.0
    slo_slow_window_s: float = 600.0


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Device-mesh layout. Axes follow the scaling-book convention:

    - ``dp``: data parallel — the profile sweep is batch-sharded over this axis
    - ``tp``: tensor parallel — attention heads / MLP hidden sharded over this axis
    - ``sp``: sequence parallel — ring-attention shards the sequence over this axis
    """

    dp: int = 1
    tp: int = 1
    sp: int = 1

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return ("dp", "tp", "sp")

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.dp, self.tp, self.sp)

    @property
    def num_devices(self) -> int:
        return self.dp * self.tp * self.sp


@dataclasses.dataclass(frozen=True)
class Config:
    """Top-level framework configuration (reference Appendix-A schema + TPU additions)."""

    # --- reference-parity fields (SURVEY.md Appendix A) ---
    random_seed: int = 42
    results_dir: str = "results"
    data_dir: str = "data/ml-1m"
    # Counterfactual attribute grid (reference ``SENSITIVE_ATTRIBUTES``; values
    # confirmed from results/phase1/phase1_results.json profiles).
    genders: Tuple[str, ...] = ("male", "female", "non-binary")
    age_groups: Tuple[str, ...] = ("18-24", "25-34", "35-44", "45-54", "55+")
    occupation: str = "professional"
    profiles_per_combo: int = 3
    conformal_alpha: float = 0.1
    bias_reduction_target: float = 50.0  # percent
    accuracy_preservation_min: float = 70.0  # percent
    # Reference ``DEFAULT_MODELS`` (phase1/3: one model; phase2: a sweep).
    # 'simulated' = the deterministic fake backend; real model names (llama3-8b
    # etc.) need --weights-dir to produce meaningful text.
    default_model_phase1: str = "simulated"
    default_models_phase2: Tuple[str, ...] = ("simulated",)
    default_model_phase3: str = "simulated"
    model_settings: Tuple[Tuple[str, ModelSettings], ...] = (
        ("tiny-test", ModelSettings(temperature=0.7, max_tokens=128)),
        ("tiny-gpt2", ModelSettings(temperature=0.7, max_tokens=128)),
        ("tiny-llama-study", ModelSettings(temperature=0.7, max_tokens=64)),
        ("tiny-gpt2-study", ModelSettings(temperature=0.7, max_tokens=64)),
        ("gpt2-small", ModelSettings(temperature=0.7, max_tokens=256)),
        ("llama32-1b", ModelSettings(temperature=0.7, max_tokens=500)),
        ("llama32-3b", ModelSettings(temperature=0.7, max_tokens=500)),
        ("llama3-8b", ModelSettings(temperature=0.7, max_tokens=500)),
        ("llama3-70b", ModelSettings(temperature=0.7, max_tokens=500)),
        ("mistral-7b", ModelSettings(temperature=0.7, max_tokens=500)),
        ("gemma-7b", ModelSettings(temperature=0.7, max_tokens=500)),
        ("qwen2-0.5b", ModelSettings(temperature=0.7, max_tokens=500)),
        ("qwen2-7b", ModelSettings(temperature=0.7, max_tokens=500)),
    )

    # --- TPU-native additions ---
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    decode_batch_size: int = 16
    max_prompt_len: int = 512
    # Global cap on decode length: settings_for() clamps each model's
    # max_tokens to this, bounding per-sweep decode cost from one knob.
    # Default 512 >= every per-model setting, so defaults change nothing.
    max_new_tokens: int = 512
    weights_dir: Optional[str] = None  # directory of HF safetensors checkpoints
    # Weight-only quantization for served models (None = use each model
    # config's own weight_quant; "none"/"int8" = explicit override both
    # ways, so --weight-quant none can force float serving even for
    # llama3-70b-int8). The int8 mode is the capacity lever that fits
    # llama3-70b tp=8 on a v5e-8 (models/configs.py, ops/quant_matmul.py).
    weight_quant: Optional[str] = None
    checkpoint_every: int = 20  # profiles between sweep checkpoints (reference: 20)
    profile_trace_dir: Optional[str] = None  # jax.profiler trace output
    # Telemetry exporters (telemetry/): when set, the run streams lifecycle
    # events to <dir>/events.jsonl and writes a registry snapshot
    # (telemetry_snapshot.json + metrics.prom) at exit; render it with
    # `cli telemetry-report <dir>`. Instrumentation itself is always on —
    # this knob only controls the on-disk exports. See docs/OBSERVABILITY.md.
    telemetry_dir: Optional[str] = None
    # Performance attribution: timeline trace export, roofline reference,
    # SLO targets (--trace-out and the --slo-* flags). The device-step
    # timeline + compile stats + roofline gauges record regardless; this
    # only shapes exports and objectives. See docs/OBSERVABILITY.md
    # §Performance attribution.
    telemetry: TelemetryConfig = dataclasses.field(
        default_factory=TelemetryConfig
    )
    # Prompt-lookup speculative decoding for greedy sweeps (off by default:
    # the stock study settings sample at temperature 0.7, where speculation
    # cannot apply — see SpeculationConfig).
    speculation: SpeculationConfig = dataclasses.field(
        default_factory=SpeculationConfig
    )
    # Continuous-batching serving (off by default: sweeps that fit one static
    # batch shape lose nothing, and the static path remains the reference
    # numerics). --continuous on the CLI flips enabled.
    serving: ServingConfig = dataclasses.field(default_factory=ServingConfig)
    # Overload control: QoS classes + deadline-aware admission + SLO-driven
    # load shedding (--overload; needs --continuous). Off by default — the
    # serving path is byte-identical without it. See docs/SERVING.md §QoS.
    overload: OverloadConfig = dataclasses.field(
        default_factory=OverloadConfig
    )
    # Replica fleet: data-parallel engine replicas behind a health-aware
    # router (--replicas N; needs --continuous). A sick replica is fenced
    # and drained, its requests migrate to healthy replicas, and it
    # rejoins through a canary probe. See docs/SERVING.md §Replica fleet.
    fleet: FleetConfig = dataclasses.field(default_factory=FleetConfig)
    # Elastic fleet: SLO-coupled autoscaling of replica membership
    # (--autoscale; needs --continuous). Scale-up adds a canary-gated
    # standby replica; scale-down retires the lowest-load replica through
    # the drain/migration path. See docs/SERVING.md §Elastic fleet.
    autoscale: AutoscaleConfig = dataclasses.field(
        default_factory=AutoscaleConfig
    )
    # Rolling upgrades: canary+fairness-gated wave rollouts over the fleet
    # (`rollout` subcommand; needs --continuous --replicas). Off by
    # default — the fleet is byte-identical without an active rollout.
    # See docs/SERVING.md §Rollouts.
    rollout: RolloutConfig = dataclasses.field(default_factory=RolloutConfig)
    # Resilience: step watchdog + per-stage circuit breakers + graceful
    # drain/journal (off by default; --max-step-seconds/--serving-journal
    # and friends flip it on). See docs/RESILIENCE.md.
    resilience: ResilienceConfig = dataclasses.field(
        default_factory=ResilienceConfig
    )
    # Integrity: numerics guards + manifest verification + serving canary
    # (guards/canary off by default; manifest verification on — it only
    # applies where a manifest exists). See docs/RESILIENCE.md §Integrity.
    integrity: IntegrityConfig = dataclasses.field(
        default_factory=IntegrityConfig
    )

    def settings_for(self, model_name: str) -> ModelSettings:
        # The derived ``<base>-int8`` serving variants (models/configs.py)
        # decode with their base model's settings.
        base_name = model_name.removesuffix("-int8")
        for name, settings in self.model_settings:
            if name in (model_name, base_name):
                if settings.max_tokens > self.max_new_tokens:
                    settings = dataclasses.replace(
                        settings, max_tokens=self.max_new_tokens
                    )
                return settings
        raise KeyError(
            f"no decode settings for model '{model_name}'; "
            f"known: {sorted(n for n, _ in self.model_settings)}"
        )

    @property
    def sensitive_attributes(self) -> Dict[str, List[str]]:
        return {"gender": list(self.genders), "age": list(self.age_groups)}


def default_config() -> Config:
    """Build a Config, honoring environment overrides."""
    kwargs = {}
    if os.environ.get("FAIRNESS_TPU_RESULTS_DIR"):
        kwargs["results_dir"] = os.environ["FAIRNESS_TPU_RESULTS_DIR"]
    if os.environ.get("FAIRNESS_TPU_DATA_DIR"):
        kwargs["data_dir"] = os.environ["FAIRNESS_TPU_DATA_DIR"]
    if os.environ.get("FAIRNESS_TPU_SEED"):
        kwargs["random_seed"] = int(os.environ["FAIRNESS_TPU_SEED"])
    if os.environ.get("FAIRNESS_TPU_TELEMETRY_DIR"):
        kwargs["telemetry_dir"] = os.environ["FAIRNESS_TPU_TELEMETRY_DIR"]
    return Config(**kwargs)


def create_directories(config: Config) -> None:
    """mkdir side-effect helper (reference ``config.create_directories()``,
    called at ``main.py:56``)."""
    for sub in ("", "phase1", "phase2", "phase3", "visualizations"):
        os.makedirs(os.path.join(config.results_dir, sub), exist_ok=True)
