"""Model-family configurations.

Architecture hyperparameters for the open-weight families named in
``BASELINE.json.configs`` (Llama-3-8B/70B, Mistral-7B, Gemma-7B, GPT-2-small)
plus tiny test configs. All sizes are public-knowledge architecture constants.

Flags rather than subclasses select family behavior:
- ``pos_emb``: "rope" (llama/mistral/gemma), "learned" (gpt2) or "none"
- ``norm``: "rmsnorm" or "layernorm"
- ``mlp``: "glu" (SwiGLU/GeGLU) or "mlp" (GPT-2's fc->gelu->proj)
- ``embed_scale``: Gemma multiplies embeddings by sqrt(d_model)
- ``sliding_window``: Mistral's local attention span

A model whose layers are not all one kind is a subclass: latent attention
with routed experts after leading dense layers is ``SparseLatentConfig``;
delta-rule linear-attention layers that keep a fixed state per row beside
softmax layers (Kimi Delta Attention with routed experts, or Gated DeltaNet
with dense MLPs in OLMo's post-norm block) is ``LinearHybridConfig``; GQA
layers that see a window (and keep a ring of it)
beside GQA layers that see and keep every position, with QK-norm, post-norms
and routed experts after leading dense layers, is ``WindowHybridConfig``. What
each layer is and keeps is asked of the configuration (``layer_kinds`` for
the MLP, ``mixer_kinds`` for the mixer, ``layer_windows`` and
``layer_rotary`` for what a GQA layer sees and whether it rotates,
``retained_len`` for the positions the slot pool keeps of it,
``layer_cache_values`` and ``row_state_bytes`` for what the cache holds, the
parameter counts), and the dense answers stay on ``ModelConfig``, whose
fields do not change.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

#: New tokens a ``serve_prefill`` call computes per row
#: (``runtime/stepbuilder.extend_window``). Prompt buckets are multiples of
#: the sequence bucket (64 or 128), so 64 divides every ``P``; it is the
#: smallest such width that covers the study's divergent tails (17-37 ids) in
#: one window. 128 measured on the chip: ``PERF.md`` section 6, PR 29. It
#: lives here because a window layer's ring is sized against it.
EXTEND_WINDOW = 64


def ring_len(window: int) -> int:
    """Positions the slot pool keeps of a layer that sees ``window`` back:
    the window and a slack of half a window (at least one prefill window),
    rounded up to whole prefill windows. The slack buys two things. A call
    that writes ``EXTEND_WINDOW`` positions must leave its FIRST query its
    whole window (``ring >= window + EXTEND_WINDOW - 1``). And a resident row
    stays a donor until it has moved ``slack`` positions past the point where
    a new row's prompt parts from it (``scheduler._plan_prefill``): half a
    window covers a divergent tail and a whole answer of the study's traffic
    many times over, at half the bytes a second window would cost. Whole
    prefill windows, so that a call's write never wraps inside the call."""
    slack = max(window // 2, EXTEND_WINDOW)
    return -(-(window + slack) // EXTEND_WINDOW) * EXTEND_WINDOW


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    d_model: int
    d_ff: int
    head_dim: int
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    pos_emb: str = "rope"  # "rope" | "learned" | "none" (no positions at all)
    norm: str = "rmsnorm"  # "rmsnorm" | "layernorm"
    mlp: str = "glu"  # "glu" | "mlp"
    use_bias: bool = False  # biases on attention + MLP projections (gpt2 family)
    qkv_bias: bool = False  # biases ONLY on q/k/v projections (qwen2 family)
    activation: str = "silu"  # "silu" | "gelu" | "gelu_tanh"
    embed_scale: bool = False  # gemma: scale embeddings by sqrt(d_model)
    tie_embeddings: bool = False
    sliding_window: Optional[int] = None  # mistral
    eos_token_id: int = 2
    pad_token_id: int = 0
    dtype: str = "bfloat16"
    # Pallas flash-attention for prefill/training attention on TPU (falls back
    # to the XLA path off-TPU or when shapes don't meet the 128-lane tiling).
    use_flash_attention: bool = True
    # int8 KV cache with per-vector scales: halves cache MEMORY (the enabler
    # for long-context / big-batch decode that wouldn't otherwise fit HBM).
    # Measured on v5e gpt2-small it is ~8% slower than bf16 — the dequant adds
    # work — so it's a capacity lever, not a speed lever. Opt-in.
    kv_cache_quant: bool = False
    # Pallas fused decode-step attention (ops/decode_attention.py): keeps the
    # per-layer scores/softmax/PV in VMEM instead of XLA's separate fusions.
    # MEASURED SLOWER both ways and OFF by default: bf16 104 vs 112
    # profiles/s at batch 48 (round 3); int8-cache mode (dequant-in-tile,
    # round 4) 0.28x XLA at batch 192/360 — the per-step head-major cache
    # transposes dominate, and the native head-major layout was also
    # measured and rejected (ROADMAP.md D4). Kept oracle-tested; the
    # bench A/Bs it every round. Applies only on TPU to single-token cached
    # steps with compatible shapes (no sliding window); all other paths use
    # XLA regardless.
    use_decode_attention_kernel: bool = False
    # Weight-only quantization for serving: "int8" stores every 2D matmul
    # kernel (q/k/v/o, gate/up/down, untied lm_head) as int8 with per-output-
    # channel float32 scales, dequantized INSIDE the Pallas matmul tile loop
    # (ops/quant_matmul.py) so no bf16 copy of the tree ever exists in HBM —
    # the capability that fits Llama-3-70B tp=8 on one v5e-8 slice (bf16 is
    # 17.6 GB/chip vs 16 GB HBM; int8 is ~9.1 GB). Embeddings, norms, and
    # biases stay in the float dtype. Serving-only: the train step rejects it.
    weight_quant: str = "none"  # "none" | "int8"
    # "xla" (default): dense/flash attention, GSPMD decides any resharding.
    # "ring": exact ring attention over the sp axis — the forward must run
    # inside shard_map with axis "sp" bound and activations sequence-sharded
    # (train/step.py sequence_parallel=True). No-cache path only.
    attention_impl: str = "xla"

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    # -- what each layer is and keeps ----------------------------------------
    # One kind of layer here (GQA attention, one dense MLP); a configuration
    # with another pattern is a subclass that answers the same questions
    # (``SparseLatentConfig``). Everything that reckons parameters or cache
    # bytes asks these, never ``2 * n_kv * head_dim`` or ``d_ff`` directly.

    @property
    def mixer_kinds(self) -> Tuple[str, ...]:
        """The mixer of each layer, in order. "gqa": softmax attention, k and
        v per KV head and position in the cache; "mla": one latent per
        position; "kda": the delta rule, one fixed state per ROW."""
        return ("gqa",) * self.num_layers

    @property
    def attention(self) -> str:
        """The one kind of mixer every layer has, or "hybrid". Whoever
        reckons in GQA's terms asks for "gqa" and so refuses a model of
        which only some layers are."""
        kinds = set(self.mixer_kinds)
        return kinds.pop() if len(kinds) == 1 else "hybrid"

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """The MLP of each layer, in order: "dense" or "moe"."""
        return ("dense",) * self.num_layers

    @property
    def heads_held(self) -> Tuple[int, int, int]:
        """(query, key/value, linear-attention) heads this chip holds of
        each layer: all of them, unless the configuration is a share."""
        return self.num_heads, self.num_kv_heads, 0

    @property
    def attention_gate(self) -> bool:
        """A sigmoid gate on a softmax layer's output, elementwise over
        heads x head_dim, before ``o_proj``."""
        return False

    @property
    def layer_windows(self) -> Tuple[Optional[int], ...]:
        """Per layer, how far back a GQA layer's query sees: the query at
        position ``p`` sees the key at ``q <= p`` iff ``p - q < window``;
        None: every earlier position. ``sliding_window`` is the every-layer
        case (Mistral); whoever has a layer in hand asks this, not the
        field."""
        return (self.sliding_window,) * self.num_layers

    @property
    def layer_rotary(self) -> Tuple[bool, ...]:
        """Per layer, whether a GQA layer rotates its queries and keys."""
        return (self.pos_emb == "rope",) * self.num_layers

    def retained_len(self, cache_len: int) -> Tuple[int, ...]:
        """Per layer, the positions the slot pool keeps of a row of
        ``cache_len``: all of them, or a ring of ``ring_len(window)`` where
        the layer sees a window and that is shorter. It follows from the
        configuration and the pool alone."""
        return tuple(cache_len if w is None else min(cache_len, ring_len(w))
                     for w in self.layer_windows)

    @property
    def qk_norm(self) -> bool:
        """An RMSNorm with a learned scale over each query and key head,
        before any rotation."""
        return False

    @property
    def qk_norm_whole(self) -> bool:
        """An RMSNorm with a learned scale over the WHOLE query projection
        and the whole key projection (every head at once, OLMo 2's), before
        any rotation."""
        return False

    @property
    def pre_norms(self) -> bool:
        """A norm on the mixer's and on the MLP's INPUT."""
        return True

    @property
    def post_norms(self) -> bool:
        """A norm on the mixer's and on the MLP's OUTPUT, before each is
        added to the stream: with ``pre_norms`` four norms a layer, without
        them OLMo 2's block (``h = x + N(Mixer(x))``)."""
        return False

    @property
    def cache_values_per_token(self) -> int:
        """Values one position-keeping layer's cache keeps for one token of
        one row."""
        return 2 * self.kv_dim

    @property
    def layer_cache_values(self) -> Tuple[int, ...]:
        """Per layer, the values its cache keeps for one POSITION of one
        row; 0 for a layer that keeps a per-row state instead."""
        return (self.cache_values_per_token,) * self.num_layers

    @property
    def row_state_bytes(self) -> int:
        """Bytes of per-row state (whatever the positions) that all the
        layers together keep for one row: 0 unless a layer is recurrent.
        The step programs, the scheduler's plan, speculation and the
        sharding rules ask this before they assume a position axis."""
        return 0

    @property
    def attention_param_count(self) -> int:
        return self.d_model * (2 * self.q_dim + 2 * self.kv_dim)

    def mixer_param_count(self, kind: str) -> int:
        """Matmul parameters of one layer's mixer as this chip holds it; one
        figure unless the mixers differ per layer."""
        return self.attention_param_count

    def mlp_param_count(self, kind: str) -> int:
        """Matmul parameters of one layer's MLP as this chip holds it."""
        if kind != "dense":
            raise ValueError(f"{type(self).__name__} has no {kind!r} layer")
        mlp_in = 2 if self.mlp == "glu" else 1
        return self.d_model * self.d_ff * (mlp_in + 1)

    @property
    def approx_param_count(self) -> int:
        """Parameter-count estimate from the architecture constants: what
        this chip holds, layer by layer."""
        embed = self.vocab_size * self.d_model
        head = 0 if self.tie_embeddings else embed
        layers = sum(self.mixer_param_count(mixer) + self.mlp_param_count(kind)
                     for mixer, kind in zip(self.mixer_kinds,
                                            self.layer_kinds))
        return embed + head + layers


@dataclasses.dataclass(frozen=True)
class RoutedExpertsConfig(ModelConfig):
    """Routed experts with a shared expert, of which this chip holds one
    rank's share: what ``SparseMLP`` asks of a configuration.

    The router scores all ``num_experts`` (sigmoid, float32), the
    ``experts_per_token`` largest are kept, their weights normalised over all
    of them and scaled by ``routed_scaling``; the layer computes the shared
    expert and those chosen experts it holds, ``experts_held``: rank
    ``expert_rank`` of ``expert_shards`` equal ranges
    (``parallel/sharding.held_experts``). What the absent ranks would add is
    left out, not stood in for.
    """

    num_experts: int = 0
    experts_per_token: int = 0
    expert_d_ff: int = 0
    num_shared_experts: int = 0
    routed_scaling: float = 1.0
    norm_topk_prob: bool = True
    expert_shards: int = 1
    expert_rank: int = 0

    @property
    def router_bias(self) -> bool:
        """A per-expert bias added to the scores to CHOOSE the experts and
        never to weigh them (a trained load-balancing buffer)."""
        return False

    def __post_init__(self):
        if self.num_experts % max(self.expert_shards, 1):
            raise ValueError(f"{self.num_experts} experts do not divide over "
                             f"{self.expert_shards} ranks")
        if not 0 <= self.expert_rank < self.expert_shards:
            raise ValueError("expert_rank outside expert_shards")

    @property
    def experts_held(self) -> Tuple[int, int]:
        """(first, count) of the routed experts this chip holds."""
        from fairness_llm_tpu.parallel.sharding import held_experts

        return held_experts(self.num_experts, self.expert_shards,
                          self.expert_rank)

    def mlp_param_count(self, kind: str) -> int:
        if kind == "moe":
            one = 3 * self.d_model * self.expert_d_ff
            return (one * (self.num_shared_experts + self.experts_held[1])
                    + self.d_model * self.num_experts)
        return super().mlp_param_count(kind)


@dataclasses.dataclass(frozen=True)
class SparseLatentConfig(RoutedExpertsConfig):
    """Latent attention (MLA) with a leading run of dense layers and then
    routed experts (``RoutedExpertsConfig``).

    ``head_dim`` is the query/key head (``qk_nope_head_dim +
    qk_rope_head_dim``); ``d_ff`` the dense layers' width.
    """

    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN (the DeepSeek-V3 convention): factor 1 is plain RoPE.
    rope_factor: float = 1.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_original_max: int = 4096
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    first_dense_layers: int = 1

    def __post_init__(self):
        super().__post_init__()
        if self.head_dim != self.qk_nope_head_dim + self.qk_rope_head_dim:
            raise ValueError("head_dim must be qk_nope_head_dim + "
                             "qk_rope_head_dim")

    @property
    def mixer_kinds(self) -> Tuple[str, ...]:
        return ("mla",) * self.num_layers

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple("dense" if i < self.first_dense_layers else "moe"
                     for i in range(self.num_layers))

    @property
    def cache_values_per_token(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def attention_param_count(self) -> int:
        d, h = self.d_model, self.num_heads
        return (d * self.q_lora_rank + self.q_lora_rank * h * self.head_dim
                + d * (self.kv_lora_rank + self.qk_rope_head_dim)
                + self.kv_lora_rank * h
                * (self.qk_nope_head_dim + self.v_head_dim)
                + h * self.v_head_dim * d)

    @property
    def softmax_scale(self) -> float:
        """``head_dim^-1/2`` times the square of YaRN's attention factor."""
        scale = self.head_dim ** -0.5
        if self.rope_factor > 1.0 and self.rope_mscale_all_dim:
            m = 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1.0
            scale *= m * m
        return scale


@dataclasses.dataclass(frozen=True)
class HeadShareConfig(RoutedExpertsConfig):
    """Mixer heads held as one rank's share, beside the experts':
    ``num_heads`` and ``num_kv_heads`` (and whatever further kinds of head a
    subclass names in ``_head_counts``) are the PUBLISHED counts, and this
    chip holds rank ``head_rank`` of ``head_shards`` equal ranges of each
    (``heads_held``), as tensor parallelism would cut them. It computes its
    own heads' part of ``o_proj``'s sum; the other ranks' is left out."""

    head_shards: int = 1
    head_rank: int = 0

    def __post_init__(self):
        super().__post_init__()
        for n in self._head_counts:
            if n % max(self.head_shards, 1):
                raise ValueError(f"{n} heads do not divide over "
                                 f"{self.head_shards} ranks")
        if not 0 <= self.head_rank < self.head_shards:
            raise ValueError("head_rank outside head_shards")

    @property
    def _head_counts(self) -> Tuple[int, int, int]:
        """Published (query, key/value, linear-attention) heads."""
        return self.num_heads, self.num_kv_heads, 0

    @property
    def heads_held(self) -> Tuple[int, int, int]:
        from fairness_llm_tpu.parallel.sharding import held_heads

        return tuple(held_heads(n, self.head_shards, self.head_rank)[1]
                     for n in self._head_counts)

    @property
    def cache_values_per_token(self) -> int:
        return 2 * self.heads_held[1] * self.head_dim


@dataclasses.dataclass(frozen=True)
class LinearHybridConfig(HeadShareConfig):
    """Delta-rule linear-attention layers beside softmax GQA layers without
    rotary positions, held as one rank's share of heads and experts
    (``HeadShareConfig``).

    Layer ``i`` is softmax GQA where ``i`` is in ``softmax_layers`` and a
    linear layer otherwise. A linear layer keeps, per row and held head, a
    ``linear_head_dim x value_head_dim`` float32 state (``row_state_shape``)
    and the last ``conv_width - 1`` inputs of its short convolution; a
    softmax layer keeps k and v per held KV head and position.

    ``linear_mixer`` names the linear layer:

    - "kda", Kimi Delta Attention (arXiv 2510.26692; the default): the decay
      per key channel through a low-rank pair of ``gate_rank``, the output
      gate ``sigmoid`` of a low-rank pair, separate q, k and v projections.
    - "gdn", Gated DeltaNet (arXiv 2412.06464, as OLMo Hybrid uses it): the
      decay one scalar per head and token from one full-rank projection, the
      output gate ``SiLU`` of a full-rank projection, and q, k and v as one
      projection, so that key widths that are no multiple of 128 lanes
      still tile. ``linear_value_head_dim`` may be wider than the key head.

    ``block`` is what is around the mixers: "kimi" (Kimi Linear's), pre-norm
    blocks with routed experts on every layer and a sigmoid gate on the
    softmax layers' output; "olmo2", OLMo 2's post-norm block (``h = x +
    N(Mixer(x))``) with a dense MLP, QK-norm over the whole projection and
    no gate.
    """

    softmax_layers: Tuple[int, ...] = ()
    linear_heads: int = 0
    linear_head_dim: int = 128
    linear_value_head_dim: int = 0  # 0: linear_head_dim, a square state
    conv_width: int = 4
    gate_rank: int = 128
    linear_mixer: str = "kda"  # "kda" | "gdn"
    block: str = "kimi"  # "kimi" | "olmo2"

    def __post_init__(self):
        super().__post_init__()
        for field, value, allowed in (
                ("linear_mixer", self.linear_mixer, ("kda", "gdn")),
                ("block", self.block, ("kimi", "olmo2"))):
            if value not in allowed:
                raise ValueError(f"{field} {value!r}: one of {allowed}")
        if self.qk_norm_whole and self.head_shards > 1:
            raise ValueError("a norm over the whole projection needs every "
                             "head's values: no share of heads")

    @property
    def _head_counts(self) -> Tuple[int, int, int]:
        return self.num_heads, self.num_kv_heads, self.linear_heads

    @property
    def mixer_kinds(self) -> Tuple[str, ...]:
        return tuple("gqa" if i in self.softmax_layers else "kda"
                     for i in range(self.num_layers))

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return ("dense" if self.block == "olmo2" else "moe",) \
            * self.num_layers

    @property
    def attention_gate(self) -> bool:
        return self.block == "kimi"

    @property
    def qk_norm_whole(self) -> bool:
        return self.block == "olmo2"

    @property
    def pre_norms(self) -> bool:
        return self.block == "kimi"

    @property
    def post_norms(self) -> bool:
        return self.block == "olmo2"

    @property
    def value_head_dim(self) -> int:
        return self.linear_value_head_dim or self.linear_head_dim

    @property
    def conv_channels(self) -> int:
        """The convolution's channels, ``[q | k | v]`` of the held heads."""
        return self.heads_held[2] * (2 * self.linear_head_dim
                                     + self.value_head_dim)

    @property
    def row_state_shape(self) -> Tuple[int, int, int]:
        """One row's state leaf in one linear layer, in the slot pool's
        layout. A per-channel decay keeps ``[heads, dk, dv]``. A per-head
        decay keeps ``p`` heads side by side, ``[heads / p, dk, p * dv]``,
        with ``p`` the fewest heads whose values fill whole 128-lane tiles
        (``ops/delta_rule.heads_side_by_side``): a 192-wide state would be
        padded to 256 lanes, two side by side fill 384 exactly."""
        from fairness_llm_tpu.ops.delta_rule import heads_side_by_side

        h, dk, dv = self.heads_held[2], self.linear_head_dim, \
            self.value_head_dim
        p = 1 if self.linear_mixer == "kda" else heads_side_by_side(h, dv)
        return h // p, dk, p * dv

    @property
    def layer_cache_values(self) -> Tuple[int, ...]:
        return tuple(self.cache_values_per_token if kind == "gqa" else 0
                     for kind in self.mixer_kinds)

    @property
    def row_state_bytes(self) -> int:
        item = 2 if self.dtype == "bfloat16" else 4
        one = (math.prod(self.row_state_shape) * 4
               + (self.conv_width - 1) * self.conv_channels * item)
        return one * self.mixer_kinds.count("kda")

    def mixer_param_count(self, kind: str) -> int:
        """Matmul, gate and tap parameters of one layer's mixer as held."""
        d = self.d_model
        hq, hkv, hl = self.heads_held
        if kind == "gqa":
            gate = hq if self.attention_gate else 0
            return d * self.head_dim * (2 * hq + gate + 2 * hkv)  # q, o; k, v
        ck, cv = hl * self.linear_head_dim, hl * self.value_head_dim
        if self.linear_mixer == "kda":
            decay = (d + ck) * self.gate_rank + ck  # the pair, dt_bias
            gate = (d + cv) * self.gate_rank
        else:
            decay, gate = d * hl + hl, d * cv  # the projection, dt_bias
        return (d * (2 * ck + cv) + cv * d + decay + gate + d * hl
                + self.conv_width * self.conv_channels + hl
                + self.value_head_dim)


@dataclasses.dataclass(frozen=True)
class WindowHybridConfig(HeadShareConfig):
    """GQA layers that see a window beside GQA layers that see everything
    (the ``afmoe`` block of Arcee's Trinity models), held as one rank's
    share of heads and experts (``HeadShareConfig``).

    Layer ``i`` is ``layer_types[i]`` (the list may run past a depth that was
    cut): a "sliding_attention" layer rotates
    its queries and keys and sees ``sliding_window`` back, so the slot pool
    keeps a ring of it (``retained_len``); a "full_attention" layer rotates
    nothing and sees, and keeps, every position. Every layer norms each
    query and key head before any rotation (``qk_norm``), gates its
    attention's output (``attention_gate``) and norms what its mixer and its
    MLP give before adding it (``post_norms``). The first
    ``first_dense_layers`` have a dense MLP of ``d_ff``, the rest routed
    experts whose choice, and only whose choice, takes a per-expert bias
    (``router_bias``).
    """

    layer_types: Tuple[str, ...] = ()
    first_dense_layers: int = 0

    def __post_init__(self):
        super().__post_init__()
        if len(self.layer_types) < self.num_layers or set(
                self.layer_types) - {"sliding_attention", "full_attention"}:
            raise ValueError("layer_types must name sliding_attention or "
                             "full_attention for each of the layers")
        if "sliding_attention" in self.layer_types and not self.sliding_window:
            raise ValueError("a sliding_attention layer needs sliding_window")

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple("dense" if i < self.first_dense_layers else "moe"
                     for i in range(self.num_layers))

    @property
    def layer_windows(self) -> Tuple[Optional[int], ...]:
        return tuple(self.sliding_window if rotates else None
                     for rotates in self.layer_rotary)

    @property
    def layer_rotary(self) -> Tuple[bool, ...]:
        return tuple(t == "sliding_attention"
                     for t in self.layer_types[:self.num_layers])

    @property
    def attention_gate(self) -> bool:
        return True

    @property
    def qk_norm(self) -> bool:
        return True

    @property
    def post_norms(self) -> bool:
        return True

    @property
    def router_bias(self) -> bool:
        return True

    def mixer_param_count(self, kind: str) -> int:
        hq, hkv, _ = self.heads_held
        return self.d_model * self.head_dim * (3 * hq + 2 * hkv)


MODEL_CONFIGS = {
    # Tiny config for tests/CI: fast to init, exercises GQA + RoPE + GLU path.
    "tiny-test": ModelConfig(
        name="tiny-test", vocab_size=512, num_layers=2, num_heads=4, num_kv_heads=2,
        d_model=64, d_ff=128, head_dim=16, max_seq_len=256, eos_token_id=1,
        dtype="float32",
    ),
    # Tiny GPT-2-style config: learned positions + layernorm + gelu MLP path.
    "tiny-gpt2": ModelConfig(
        name="tiny-gpt2", vocab_size=512, num_layers=2, num_heads=4, num_kv_heads=4,
        d_model=64, d_ff=256, head_dim=16, max_seq_len=256, pos_emb="learned",
        norm="layernorm", mlp="mlp", use_bias=True, activation="gelu_tanh",
        tie_embeddings=True, eos_token_id=1, dtype="float32",
    ),
    # Tiny *study* configs: match the transformers-built checkpoints committed
    # under checkpoints/ (tools/build_tiny_study_checkpoints.py). These exist
    # so the full --all study can run through the REAL weights path
    # (backend_for -> load_checkpoint -> HFTokenizer -> EngineBackend) end to
    # end without pretrained weights in the environment — the reference's
    # inference layer was always a real model (phase1_bias_detection.py:180-188),
    # and results/real_weights/ holds the committed record. Swapping in actual
    # Llama weights is then a config change, not new code. vocab 512 matches
    # the committed BPE tokenizer; eos/pad 0 = its <|endoftext|>.
    "tiny-llama-study": ModelConfig(
        name="tiny-llama-study", vocab_size=512, num_layers=4, num_heads=4,
        num_kv_heads=2, d_model=128, d_ff=256, head_dim=32, max_seq_len=1024,
        eos_token_id=0, pad_token_id=0, dtype="float32",
        use_flash_attention=False,
    ),
    "tiny-gpt2-study": ModelConfig(
        name="tiny-gpt2-study", vocab_size=512, num_layers=4, num_heads=4,
        num_kv_heads=4, d_model=128, d_ff=512, head_dim=32, max_seq_len=1024,
        pos_emb="learned", norm="layernorm", mlp="mlp", use_bias=True,
        activation="gelu_tanh", tie_embeddings=True, eos_token_id=0,
        pad_token_id=0, dtype="float32", use_flash_attention=False,
    ),
    # Tiny latent-attention + routed-experts config (tests): one dense layer,
    # then two expert layers of 16 experts, top-4, all held.
    "tiny-mla-moe": SparseLatentConfig(
        name="tiny-mla-moe", vocab_size=512, num_layers=3, num_heads=4,
        num_kv_heads=4, d_model=64, d_ff=128, head_dim=24, max_seq_len=256,
        eos_token_id=1, dtype="float32", use_flash_attention=False,
        q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_factor=4.0,
        rope_original_max=64, rope_mscale=1.0, rope_mscale_all_dim=1.0,
        first_dense_layers=1, num_experts=16, experts_per_token=4,
        expert_d_ff=32, num_shared_experts=1, routed_scaling=2.5,
    ),
    # Tiny linear-attention hybrid (tests): period 4 (layer 0 gated softmax
    # GQA, layers 1-3 delta rule), every layer 16 experts top-4, all held.
    "tiny-kda-moe": LinearHybridConfig(
        name="tiny-kda-moe", vocab_size=512, num_layers=4, num_heads=4,
        num_kv_heads=2, d_model=64, d_ff=128, head_dim=16, max_seq_len=256,
        eos_token_id=1, dtype="float32", use_flash_attention=False,
        pos_emb="none", softmax_layers=(0,), linear_heads=4,
        linear_head_dim=16, gate_rank=8, num_experts=16,
        experts_per_token=4, expert_d_ff=32, num_shared_experts=1,
    ),
    # Tiny Gated DeltaNet hybrid (tests): OLMo Hybrid's kinds of layer, one
    # period (three delta-rule layers, a state 16 x 64 a head, then one full
    # NoPE layer with QK-norm over the projections), post-norm blocks,
    # dense MLPs.
    "tiny-gdn": LinearHybridConfig(
        name="tiny-gdn", vocab_size=512, num_layers=4, num_heads=4,
        num_kv_heads=4, d_model=64, d_ff=128, head_dim=16, max_seq_len=256,
        norm_eps=1e-6, eos_token_id=1, dtype="float32",
        use_flash_attention=False, pos_emb="none", softmax_layers=(3,),
        linear_heads=4, linear_head_dim=16, linear_value_head_dim=64,
        linear_mixer="gdn", block="olmo2",
    ),
    # Tiny window/full hybrid (tests): two periods of three window layers
    # (rotary, window 16, a ring of 128 in the slot pool) and one full layer
    # (no rotary); layers 0-1 dense, then 16 experts top-4, all held.
    "tiny-window-moe": WindowHybridConfig(
        name="tiny-window-moe", vocab_size=512, num_layers=8, num_heads=4,
        num_kv_heads=2, d_model=64, d_ff=128, head_dim=16, max_seq_len=1024,
        eos_token_id=1, dtype="float32", use_flash_attention=False,
        embed_scale=True, sliding_window=16,
        layer_types=("sliding_attention",) * 3 + ("full_attention",)
        + ("sliding_attention",) * 3 + ("full_attention",),
        first_dense_layers=2, num_experts=16, experts_per_token=4,
        expert_d_ff=32, num_shared_experts=1, routed_scaling=2.5,
    ),
    "gpt2-small": ModelConfig(
        name="gpt2-small", vocab_size=50257, num_layers=12, num_heads=12,
        num_kv_heads=12, d_model=768, d_ff=3072, head_dim=64, max_seq_len=1024,
        pos_emb="learned", norm="layernorm", mlp="mlp", use_bias=True,
        activation="gelu_tanh", tie_embeddings=True, eos_token_id=50256,
        pad_token_id=50256,
    ),
    # Llama-3.2 small models: the single-chip-friendly members of the family
    # (1B/3B fit a v5e chip in bf16 with room for KV cache and batch).
    "llama32-1b": ModelConfig(
        name="llama32-1b", vocab_size=128256, num_layers=16, num_heads=32,
        num_kv_heads=8, d_model=2048, d_ff=8192, head_dim=64, max_seq_len=8192,
        rope_theta=500000.0, tie_embeddings=True, eos_token_id=128001,
        pad_token_id=128001,
    ),
    "llama32-3b": ModelConfig(
        name="llama32-3b", vocab_size=128256, num_layers=28, num_heads=24,
        num_kv_heads=8, d_model=3072, d_ff=8192, head_dim=128, max_seq_len=8192,
        rope_theta=500000.0, tie_embeddings=True, eos_token_id=128001,
        pad_token_id=128001,
    ),
    "llama3-8b": ModelConfig(
        name="llama3-8b", vocab_size=128256, num_layers=32, num_heads=32,
        num_kv_heads=8, d_model=4096, d_ff=14336, head_dim=128, max_seq_len=8192,
        rope_theta=500000.0, eos_token_id=128001, pad_token_id=128001,
    ),
    "llama3-70b": ModelConfig(
        name="llama3-70b", vocab_size=128256, num_layers=80, num_heads=64,
        num_kv_heads=8, d_model=8192, d_ff=28672, head_dim=128, max_seq_len=8192,
        rope_theta=500000.0, eos_token_id=128001, pad_token_id=128001,
    ),
    "mistral-7b": ModelConfig(
        name="mistral-7b", vocab_size=32000, num_layers=32, num_heads=32,
        num_kv_heads=8, d_model=4096, d_ff=14336, head_dim=128, max_seq_len=8192,
        rope_theta=1000000.0, sliding_window=4096, eos_token_id=2, pad_token_id=0,
    ),
    # OLMo Hybrid 7B (allenai/Olmo-Hybrid-7B config.json): 24 Gated DeltaNet
    # layers (30 heads, a 96 x 192 state each) and 8 full NoPE layers (30
    # heads of 128, QK-norm over the projections), three linear to one;
    # post-norm blocks, SwiGLU 11008, no experts.
    "olmo-hybrid-7b": LinearHybridConfig(
        name="olmo-hybrid-7b", vocab_size=100352, num_layers=32,
        num_heads=30, num_kv_heads=30, d_model=3840, d_ff=11008,
        head_dim=128, max_seq_len=65536, norm_eps=1e-6, pos_emb="none",
        use_flash_attention=False,
        softmax_layers=tuple(range(3, 32, 4)), linear_heads=30,
        linear_head_dim=96, linear_value_head_dim=192, linear_mixer="gdn",
        block="olmo2",
    ),
    "gemma-7b": ModelConfig(
        name="gemma-7b", vocab_size=256000, num_layers=28, num_heads=16,
        num_kv_heads=16, d_model=3072, d_ff=24576, head_dim=256, max_seq_len=8192,
        activation="gelu_tanh", embed_scale=True, tie_embeddings=True,
        eos_token_id=1, pad_token_id=0,
    ),
    # Qwen2/2.5: llama-like (RMSNorm + SwiGLU + RoPE + GQA) with biases on
    # the q/k/v projections only (qkv_bias).
    "qwen2-0.5b": ModelConfig(
        name="qwen2-0.5b", vocab_size=151936, num_layers=24, num_heads=14,
        num_kv_heads=2, d_model=896, d_ff=4864, head_dim=64, max_seq_len=8192,
        rope_theta=1000000.0, norm_eps=1e-6, qkv_bias=True, tie_embeddings=True,
        eos_token_id=151643, pad_token_id=151643,
    ),
    "qwen2-7b": ModelConfig(
        name="qwen2-7b", vocab_size=152064, num_layers=28, num_heads=28,
        num_kv_heads=4, d_model=3584, d_ff=18944, head_dim=128, max_seq_len=8192,
        rope_theta=1000000.0, norm_eps=1e-6, qkv_bias=True,
        eos_token_id=151643, pad_token_id=151643,
    ),
}

# int8 weight-only serving variants, DERIVED from their base entries (not
# hand-copied — a fix to a base architecture constant must not need applying
# twice). These are the configs that make the BASELINE.json targets actually
# fit v5e HBM with dequant-in-tile weights (ops/quant_matmul.py):
#   llama3-8b-int8   ~8.6 GB  — BASELINE configs[1] on ONE 15.75 GB chip
#                               (bf16 8B is ~16 GB of params alone)
#   llama3-70b-int8  ~9.0 GB/chip at tp=8 on a v5e-8 (bf16 is 17.6 GB/chip;
#                               proven in tests/test_70b_readiness.py)
#   mistral-7b-int8  ~7.4 GB, qwen2-7b-int8 ~8.2 GB, gemma-7b-int8 ~9.3 GB
#                    — the configs[2] cross-model set, single chip each
#                      (tied embeddings, e.g. gemma's 256k vocab, stay bf16)
for _base in ("llama3-8b", "llama3-70b", "mistral-7b", "gemma-7b", "qwen2-7b",
              "olmo-hybrid-7b"):
    _cfg = MODEL_CONFIGS[_base]
    MODEL_CONFIGS[f"{_base}-int8"] = dataclasses.replace(
        _cfg, name=f"{_base}-int8", weight_quant="int8"
    )
del _base, _cfg


def get_model_config(name: str) -> ModelConfig:
    if name not in MODEL_CONFIGS:
        raise KeyError(f"unknown model '{name}'; available: {sorted(MODEL_CONFIGS)}")
    return MODEL_CONFIGS[name]
