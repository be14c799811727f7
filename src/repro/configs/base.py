"""Model / run configuration dataclasses.

Every assigned architecture gets a module in this package exposing
``CONFIG`` (the exact full-size config) and ``smoke_config()`` (a reduced
variant of the same family: <=2 layers-per-period repeats, d_model<=512,
<=4 experts) used by CPU smoke tests.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax.numpy as jnp


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                       # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                 # 0 -> d_model // num_heads

    # --- attention options -------------------------------------------------
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    sliding_window: int = 0           # 0 = full attention
    causal: bool = True

    # --- MLA (DeepSeek/MiniCPM3-style latent attention) ---------------------
    mla: bool = False
    q_lora_rank: int = 0              # 0 = a plain query projection wq
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # --- YaRN rope scaling (DeepSeek-V2's static blend) -----------------------
    yarn_factor: float = 0.0          # 0 = plain rope
    yarn_original_max_position: int = 0
    yarn_mscale_all_dim: float = 0.0  # the temperature term; 0 = none, the
                                      # softmax scale left as hd**-0.5. cos
                                      # and sin stay unscaled, as where the
                                      # published mscale equals it

    # --- MoE ----------------------------------------------------------------
    moe: bool = False
    num_experts: int = 0
    num_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0                 # per-expert hidden dim
    router_experts: int = 0           # router width; 0 -> num_experts. A
                                      # layer that holds num_experts of
                                      # them (experts 0..num_experts-1)
                                      # routes over all router_experts and
                                      # computes only its own experts' part
    norm_topk_prob: bool = True       # renormalise the top-k weights to 1
    moe_every: int = 1                # MoE FFN on layers where (i % moe_every == moe_offset)
    moe_offset: int = 0
    router_aux_coef: float = 0.01

    # --- layer pattern ------------------------------------------------------
    # "attn"  : homogeneous attention blocks
    # "jamba" : period 8 = [attn, mamba x7]; MoE every other layer
    # "xlstm" : period 2 = [mlstm, slstm]
    pattern: str = "attn"
    first_dense: int = 0              # leading layers with dense FFN (DeepSeek-MoE: 1)

    # --- SSM (mamba) ----------------------------------------------------------
    ssm_state_dim: int = 16
    ssm_conv_dim: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: int = 0              # 0 -> ceil(d_model/16)
    ssm_chunk: int = 256              # chunked-scan length (train/prefill)

    # --- xLSTM ----------------------------------------------------------------
    mlstm_proj_factor: float = 2.0
    slstm_proj_factor: float = 4.0 / 3.0

    # --- encoder/decoder (whisper) -------------------------------------------
    encdec: bool = False
    encoder_layers: int = 0
    num_frames: int = 1500            # stubbed conv-frontend output length

    # --- VLM (llava) -----------------------------------------------------------
    vlm: bool = False
    num_image_tokens: int = 0         # stubbed ViT/projector output tokens

    # --- numerics --------------------------------------------------------------
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    tie_embeddings: bool = True

    # --- execution knobs ---------------------------------------------------------
    attn_block_q: int = 512           # flash-attention query block
    attn_block_kv: int = 1024         # flash-attention kv block
    loss_chunk: int = 512             # chunked softmax-xent sequence chunk
    remat: bool = True                # checkpoint each scanned period
    remat_policy: str = "full"        # full | save_mixer (keep attention/scan
                                      # outputs; don't recompute them in bwd)
    # beyond-paper perf knobs (EXPERIMENTS.md SSPerf):
    seq_shard_attn: bool = False      # sequence-parallel attention: shard S over
                                      # "model" when heads % model_axis != 0
    attn_bf16: bool = False           # bf16 qk^T / pv matmuls (f32 softmax state)
    expert_parallel: bool = False     # shard MoE experts (not dff) over "model"
    dp_axes: tuple = ("data",)        # data-parallel mesh axes for constraints

    # --- citation / provenance ------------------------------------------------
    source: str = ""

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        if self.ssm_dt_rank == 0:
            object.__setattr__(self, "ssm_dt_rank", -(-self.d_model // 16))
        validate_model(self)

    # ------------------------------------------------------------------ helpers
    @property
    def router_width(self) -> int:
        """Experts the router scores (``router_experts``, else all held)."""
        return self.router_experts or self.num_experts

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def period(self) -> int:
        return {"attn": 1, "jamba": 8, "xlstm": 2}[self.pattern]

    @property
    def n_periods(self) -> int:
        n = self.num_layers - self.first_dense
        assert n % self.period == 0, (self.name, self.num_layers, self.period)
        return n // self.period

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    def layer_kinds(self) -> list[dict]:
        """Blocks of one period, in order. kind: mixer + ffn type."""
        if self.pattern == "attn":
            return [{"mixer": "attn", "ffn": "moe" if self.moe else "dense"}]
        if self.pattern == "jamba":
            kinds = []
            for i in range(8):
                mixer = "attn" if i == 0 else "mamba"
                ffn = "moe" if (self.moe and i % self.moe_every == self.moe_offset) else "dense"
                kinds.append({"mixer": mixer, "ffn": ffn})
            return kinds
        if self.pattern == "xlstm":
            # xLSTM blocks are self-contained (d_ff = 0): no separate FFN.
            return [{"mixer": "mlstm", "ffn": "none"}, {"mixer": "slstm", "ffn": "none"}]
        raise ValueError(self.pattern)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


def validate_model(cfg: ModelConfig) -> None:
    """The ModelConfig fields that must agree with each other; raises
    ``ValueError``. Runs on every construction and ``replace``."""
    if cfg.moe:
        if not 0 < cfg.top_k <= cfg.router_width:
            raise ValueError(f"{cfg.name}: top_k={cfg.top_k} must lie in "
                             f"1..{cfg.router_width} (the router's width)")
        if not 0 < cfg.num_experts <= cfg.router_width:
            raise ValueError(
                f"{cfg.name}: the layer holds num_experts={cfg.num_experts} "
                f"of router_experts={cfg.router_experts}; it must hold 1 to "
                "all of them")
    if cfg.yarn_factor:
        if cfg.yarn_factor <= 1 or cfg.yarn_original_max_position <= 0:
            raise ValueError(
                f"{cfg.name}: YaRN needs yarn_factor > 1 and "
                "yarn_original_max_position > 0, got "
                f"{cfg.yarn_factor} and {cfg.yarn_original_max_position}")


@dataclass(frozen=True)
class InputShape:
    """One of the assigned (seq_len, global_batch) workload points."""
    name: str
    seq_len: int
    global_batch: int
    kind: str                         # "train" | "prefill" | "decode"


INPUT_SHAPES: dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


@dataclass(frozen=True)
class FedConfig:
    """FedALIGN / federation hyper-parameters (paper §3-4)."""
    num_clients: int = 60
    num_priority: int = 2
    local_epochs: int = 5             # E
    epsilon: float = 0.2              # selection threshold eps_t
    epsilon_decay: float = 0.0        # eps_t = epsilon * (1 - decay)^round (fine-tuning)
    epsilon_schedule: str = "constant"  # constant | linear | exp | step
    warmup_frac: float = 0.1          # priority-only warm-up rounds
    rounds: int = 100
    lr: float = 0.1
    lr_schedule: str = "constant"     # constant | paper_decay (2/(mu(t+gamma)))
    mu_strong: float = 1.0            # mu for paper_decay
    gamma_decay: float = 10.0         # gamma for paper_decay
    participation: float = 1.0        # fraction sampled per round (<1 = partial)
    straggler_period: int = 0         # >0: non-priority client k only shows up
                                      # every (2 + k % period) rounds — the
                                      # paper's App. A.4 arbitrary-participation
                                      # model (stragglers)
    candidate_pool: int = 0           # sample-then-evaluate population scaling
                                      # (cross-device regime of arXiv:
                                      # 2211.01549): each round draws a
                                      # candidate pool of P clients — priority
                                      # clients always in-pool, the remaining
                                      # P - num_priority sampled without
                                      # replacement from the round PRNG
                                      # stream — and ONLY the [P] slice pays
                                      # the eval pre-pass, gating, cohort
                                      # gather, training, and the fused
                                      # fedagg; the dense [C] state leaves
                                      # (backlog, util/incl EMAs, ef_accum)
                                      # are touched by gather/scatter at the
                                      # sampled indices only, so round cost
                                      # is O(P), flat in C. 0 disables
                                      # pooling; P >= num_clients also runs
                                      # the dense round (everyone is a
                                      # candidate) — both are bit-identical
                                      # to the legacy trace. Requires
                                      # P >= num_priority when on
    pool_weighting: str = "uniform"   # candidate-pool sampling weights for
                                      # the non-priority draw (Gumbel top-k,
                                      # i.e. sampling without replacement
                                      # proportional to the weight):
                                      # "uniform" — every non-priority client
                                      # equally likely | "backlog" — weight
                                      # 1 + backlog_k, so clients starved by
                                      # cohort overflow re-enter the pool
                                      # sooner | "ema" — weight
                                      # (1 + tiny) - incl_ema_k, so rarely-
                                      # included clients are re-sampled and
                                      # their utility estimate keeps
                                      # refreshing
    algorithm: str = "fedavg"         # local solver: fedavg | fedprox
    prox_mu: float = 1.0              # FedProx proximal coefficient
    selection: str = "fedalign"       # SelectionStrategy name (fl/engine.py
                                      # registry): fedalign | all |
                                      # priority_only | topk_align | grad_sim
                                      # | welfare
    topk: int = 4                     # topk_align budget: at most k best
                                      # loss-matched non-priority clients
    sim_threshold: float = 0.0        # grad_sim: min cosine(delta_k, delta_P)
    grad_sim_sketch: bool = False     # grad_sim: score clients on a
                                      # CountSketch random projection of
                                      # their delta instead of the exact
                                      # [C, M_total] flatten (streaming-
                                      # friendly; JL-approximate cosines)
    sketch_dim: int = 256             # sketch width for grad_sim_sketch and
                                      # the temporal (FSDP) grad_sim round
    utility_ema: float = 0.9          # decay beta of the cross-round client
                                      # utility EMAs (loss-gap + inclusion
                                      # history) carried in FederationState
    welfare_floor: float = 0.0        # welfare strategy: non-priority
                                      # clients whose inclusion EMA fell
                                      # below this floor are admitted even
                                      # when their smoothed loss gap is
                                      # outside eps_t (fairness floor after
                                      # Travadi et al., arXiv:2302.08976);
                                      # 0 disables the floor
    backend: str = "vmap_spatial"     # engine execution backend:
                                      # vmap_spatial (clients in parallel) |
                                      # scan_temporal (time-multiplexed) |
                                      # scan_async (overlapped cohorts: the
                                      # round's aggregated delta is applied
                                      # async_depth rounds later)
    async_depth: int = 0              # scan_async pipeline depth D: the
                                      # cohort gathered at round t trains
                                      # against w_t but its aggregated delta
                                      # is applied at round t + D, while
                                      # rounds t+1..t+D-1 evaluate/gate
                                      # without waiting for it. The D
                                      # in-flight deltas live in
                                      # FederationState.inflight (a ring
                                      # buffer, oldest first). 0 = fully
                                      # synchronous: scan_async is then
                                      # bit-identical to vmap_spatial
    staleness_decay: float = 1.0      # per-round discount on stale deltas:
                                      # a delta applied with staleness s is
                                      # scaled by staleness_decay ** s
                                      # before the ServerOptimizer step
                                      # (1.0 = no discount; cf. async FL
                                      # buffers, arXiv:2402.05050). Under
                                      # async_mode="fifo" s is always the
                                      # constant async_depth; under "ready"
                                      # s is the slot's measured age
    async_mode: str = "fifo"          # in-flight pop policy (scan_async):
                                      # "fifo"  — strict fixed-lag pipe:
                                      #   every delta ages exactly
                                      #   async_depth rounds (the PR 4
                                      #   pipeline, bit-identical)
                                      # "ready" — FedBuff-style variable
                                      #   lag: any slot whose age reached
                                      #   min_lag is applied, oldest first,
                                      #   possibly several per round; the
                                      #   buffer only fills to min_lag in
                                      #   steady state, async_depth is its
                                      #   capacity
    min_lag: int = 1                  # async_mode="ready": minimum rounds a
                                      # buffered delta must age before it
                                      # may be applied (its readiness
                                      # threshold). Must satisfy
                                      # 1 <= min_lag <= async_depth (a
                                      # delta can never pop the round it
                                      # was pushed, so 0 would silently
                                      # mean 1); a full buffer with no
                                      # ready slot force-pops the oldest
                                      # (FedBuff overflow rule)
    latency_mode: str = "none"        # per-client latency model for the
                                      # event-driven clock: "none" (disabled:
                                      # no latency leaves, no timers — the
                                      # pinned fixed-lag behaviour) |
                                      # "lognormal" (compute + network times
                                      # drawn ONCE per client at init_state,
                                      # in round units, from the latency_*
                                      # knobs; systems-heterogeneity model of
                                      # arXiv:2211.01549). With scan_async it
                                      # requires async_mode="ready": each
                                      # pushed slot carries a countdown timer
                                      # set by its SLOWEST surviving member
                                      # and lands when the timer expires, so
                                      # staleness becomes a measured
                                      # distribution instead of a fixed depth
    latency_mu: float = 0.0           # lognormal compute-time log-mean
    latency_sigma: float = 0.5        # lognormal compute-time log-std (>= 0)
    latency_net_mu: float = -1.0      # lognormal network-time log-mean
    latency_net_sigma: float = 0.3    # lognormal network-time log-std (>= 0)
    round_deadline: float = float("inf")  # deadline (round units) on simulated
                                      # completion times: clients slower than
                                      # the deadline are dropped from the
                                      # round's aggregate (partial-cohort
                                      # landing through the zero-mass-safe
                                      # fedagg path) and re-enqueued via the
                                      # backlog; under the event clock the
                                      # slot timer is capped at
                                      # ceil(round_deadline). Requires a
                                      # latency model; must be > 0 (a zero/
                                      # negative deadline would force-land
                                      # every slot empty — rejected by
                                      # check_clock_config)
    failure_model: str = "none"       # FailureModel registry name
                                      # (fl/engine.py): none | crash (per-
                                      # round Bernoulli: delta lost AFTER
                                      # training, mass masked, backlog
                                      # re-enqueue) | dropout (client
                                      # unavailable for dropout_len-round
                                      # windows, folded into the
                                      # participation mask) | corrupt
                                      # (delta rows NaN'd or scaled in
                                      # transit via the delta_transform
                                      # seam) | chaos (all three composed).
                                      # Keyed from fold_in(seed,
                                      # "failure_model") x absolute round —
                                      # bit-reproducible and resume-safe
    crash_rate: float = 0.0           # crash/chaos: per-client per-round
                                      # Bernoulli crash probability in [0, 1]
    dropout_rate: float = 0.0         # dropout/chaos: probability in [0, 1]
                                      # a client sits out a whole window
    dropout_len: int = 1              # dropout/chaos: window length k >= 1
                                      # (rounds) of a transient drop-out
    corrupt_rate: float = 0.0         # corrupt/chaos: per-client per-round
                                      # corruption probability in [0, 1]
    corrupt_scale: float = 0.0        # corrupt/chaos: corrupted deltas are
                                      # scaled by this factor; 0.0 means the
                                      # payload is garbled to NaN instead
                                      # (the divergence guard's target)
    divergence_guard: bool = False    # detect non-finite aggregated deltas /
                                      # eval loss inside the scanned driver
                                      # and lax.cond-skip the apply (bit-
                                      # exact no-op, like the zero-inclusion
                                      # skip); consecutive skips counted in
                                      # the nonfinite_skips state leaf and
                                      # surfaced as stats["skipped_nonfinite"]
    max_nonfinite_skips: int = 0      # divergence_guard: run_federation
                                      # halts-and-reports once this many
                                      # CONSECUTIVE rounds skipped on
                                      # non-finite aggregates (0 = never
                                      # halt, guard still skips/counts)
    adaptive_staleness: bool = False  # discount stale deltas by MEASURED
                                      # drift instead of age alone: each
                                      # applied delta is scaled by
                                      # staleness_decay**age *
                                      # max(0, cos(delta, last applied
                                      # delta)), with the cosine estimated
                                      # on sketch_dim CountSketches (the
                                      # last_delta leaf in FederationState).
                                      # False keeps the constant schedule
                                      # (the pinned PR 4 fallback)
    max_cohort: int = 0               # static training-cohort budget K for
                                      # gate-before-train strategies (those
                                      # not needing client deltas): gates are
                                      # computed from the cheap eval pre-pass,
                                      # the K included clients are gathered
                                      # into a dense [K, ...] buffer, and only
                                      # they run E local epochs. 0 disables
                                      # the gather (train everyone; gated-out
                                      # updates dropped at aggregation).
                                      # Overflow policy: if more than K
                                      # clients gate in, priority clients are
                                      # kept first, then the best loss-matched
                                      # non-priority clients; the worst-
                                      # matched overflow is dropped for the
                                      # round (deterministic, stable order)
    backlog_boost: float = 0.0        # cohort overflow priority boost: the
                                      # cohort rank becomes
                                      # |F_k - F| - backlog_boost * backlog,
                                      # so a starved-but-close client can
                                      # OUTRANK a slightly better-matched
                                      # one instead of only winning exact
                                      # ties (float match qualities almost
                                      # never tie exactly). 0.0 keeps the
                                      # pinned tie-break-only policy
                                      # bit-identical
    align_stat: str = "accuracy"      # accuracy (paper experiments) | loss (theory)
    server_opt: str = "none"          # ServerOptimizer registry name
                                      # (core/aggregation.py): sgd (= the
                                      # legacy "none") | momentum (FedAvgM)
                                      # | adam (FedAdam) | yogi (FedYogi),
                                      # applied to the fused aggregated
                                      # delta; moments persist across
                                      # rounds in FederationState.opt_state
    server_lr: float = 1.0
    server_momentum: float = 0.9
    aggregator: str = "mean"          # Aggregator registry name
                                      # (core/aggregation.py): how the gated
                                      # client deltas are REDUCED, always in
                                      # the one fused fedagg kernel launch:
                                      # mean (paper eq. (15), default) |
                                      # trimmed_mean | median (coordinate-
                                      # wise robust order statistics,
                                      # unweighted over included clients) |
                                      # dp (per-client L2 clip + Gaussian
                                      # noise, DP-FedAvg) | cosine_filter
                                      # (drop delta-sketch outliers, then
                                      # mean)
    trim_frac: float = 0.1            # trimmed_mean: fraction of the n
                                      # included clients trimmed from EACH
                                      # side per coordinate
                                      # (floor(trim_frac * n); must be
                                      # < 0.5). Robust to up to
                                      # floor(trim_frac * n) Byzantine
                                      # clients
    dp_clip: float = 1.0              # dp: per-client delta L2 clip bound S
                                      # (the DP sensitivity); clients over
                                      # the bound are scaled down, never up
    dp_noise: float = 0.0             # dp: noise multiplier z — per-
                                      # coordinate sigma is
                                      # z * dp_clip / inclusion_mass on the
                                      # renormalized mean. 0 = clip-only.
                                      # (eps, delta) over rounds comes from
                                      # the RDP accountant (dp_epsilon in
                                      # core/aggregation.py) at dp_delta
    dp_delta: float = 1e-5            # dp: target delta for the reported
                                      # (epsilon, delta) privacy budget
    outlier_cos: float = 0.0          # cosine_filter: clients whose sketch-
                                      # estimated delta-direction cosine to
                                      # the gated mean direction falls
                                      # BELOW this are gated out for the
                                      # round (0 drops anti-correlated
                                      # deltas; sketches are sketch_dim
                                      # CountSketches)
    server_b1: float = 0.9            # adam/yogi first-moment decay
    server_b2: float = 0.99           # adam/yogi second-moment decay
                                      # (FedOpt paper default)
    server_eps: float = 1e-3          # adam/yogi denominator floor (tau)
    agg_dtype: str = "float32"        # dtype of aggregated client DELTAS on the
                                      # wire (bfloat16 halves FedALIGN's
                                      # aggregation collective — beyond-paper)
    wire_codec: str = "identity"      # WireCodec registry name
                                      # (core/aggregation.py): lossy uplink
                                      # compression of the fused [C, M_total]
                                      # client-delta buffer, decoded INSIDE
                                      # the one fedagg kernel launch:
                                      # identity (no codec — the pinned
                                      # legacy wire, agg_dtype only) | int8
                                      # (symmetric per-client-row int8 with
                                      # one f32 scale per client,
                                      # dequantize-in-register) | topk (per-
                                      # client magnitude top-k
                                      # sparsification, sparse-scatter-
                                      # accumulate) | sketch (CountSketch
                                      # rows — delta_sketch infra — decoded
                                      # by hash/sign gather). Non-identity
                                      # codecs carry per-client error-
                                      # feedback accumulators in
                                      # FederationState.ef_accum (see
                                      # error_feedback)
    error_feedback: bool = True       # non-identity wire_codec: carry the
                                      # per-client compression residual
                                      # x - decode(encode(x)) in
                                      # FederationState.ef_accum and add it
                                      # to the NEXT round's delta before
                                      # encoding (EF / EF21-style memory),
                                      # so compression bias is re-injected
                                      # instead of lost and convergence
                                      # doesn't stall. Updates at PUSH time
                                      # under scan_async (when the delta is
                                      # encoded, not when it lands). Ignored
                                      # by identity
    codec_topk_frac: float = 0.01     # topk codec: fraction of M_total kept
                                      # per client row (k = max(1,
                                      # floor(frac * M)); values + int32
                                      # indices travel the wire). Must be in
                                      # (0, 1]
    codec_sketch_dim: int = 2048      # sketch codec: CountSketch width per
                                      # client row (the uplink is [C,
                                      # codec_sketch_dim] f32; one shared
                                      # hash/sign stream per run keyed from
                                      # fold_in(seed, "wire_sketch")). Must
                                      # be >= 1
    fused_agg: bool = True            # flatten the whole client-stacked pytree
                                      # to [C, M_total]: ONE fedagg call per
                                      # round instead of one per leaf
    batch_size: int = 32              # local minibatch
    seed: int = 0

    def replace(self, **kw) -> "FedConfig":
        return dataclasses.replace(self, **kw)


# --------------------------------------------------------------------------
# Config validation: ONE entry point, decorator-registered subsystem hooks.
#
# The async, clock, aggregator, and codec checks used to be four scattered
# ``check_*_config`` functions every caller had to know to call (and in the
# right combination); now each subsystem contributes its check with
# ``@register_validator("name")`` at import time and every round builder /
# driver / CLI calls the single ``validate_config(fed)``. The old names
# survive as thin deprecated aliases of the registered hooks.
_VALIDATORS: dict = {}


def register_validator(name: str):
    """Decorator: contribute a subsystem's FedConfig check to
    ``validate_config``. The hook takes ``fed`` and raises ``ValueError``
    (with an actionable message) on an invalid knob combination; hooks run
    in sorted-name order, so error precedence is deterministic."""
    def deco(fn):
        _VALIDATORS[name] = fn
        return fn
    return deco


def validate_config(fed: "FedConfig") -> "FedConfig":
    """Run every registered subsystem validator against ``fed``.

    Returns ``fed`` unchanged so call sites can validate inline:
    ``fed = validate_config(fed)``. Importing the standard subsystems here
    (they register their hooks at import) means a bare
    ``validate_config(fed)`` never silently skips checks the caller's
    import graph happened not to pull in."""
    from repro.core import aggregation  # noqa: F401  (registers hooks)
    from repro.fl import engine         # noqa: F401  (registers hooks)
    for name in sorted(_VALIDATORS):
        _VALIDATORS[name](fed)
    return fed


@register_validator("population")
def check_pool_config(fed: "FedConfig") -> None:
    """Candidate-pool knobs (the population-scaling subsystem's hook)."""
    if fed.candidate_pool < 0:
        raise ValueError(
            f"candidate_pool must be >= 0, got {fed.candidate_pool} "
            "(0 disables pooling)")
    if fed.pool_weighting not in ("uniform", "backlog", "ema"):
        raise ValueError(
            f"unknown pool_weighting {fed.pool_weighting!r}; "
            "valid: ['backlog', 'ema', 'uniform']")
    if 0 < fed.candidate_pool < fed.num_priority:
        raise ValueError(
            f"candidate_pool={fed.candidate_pool} is smaller than "
            f"num_priority={fed.num_priority}: priority clients are always "
            "in-pool, so the pool must hold at least all of them")
