"""Config system: typed, frozen dataclasses + the four assigned input shapes.

Every architecture in src/repro/configs/ builds a ModelConfig; launchers
combine it with a ShapeConfig (one of the four assigned input shapes), a
MeshConfig and — for training — a FedConfig selecting the federated
algorithm (FedGiA or one of the paper's comparison baselines).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture definition (decoder-only backbone).

    Families: dense | moe | ssm | hybrid | vlm | audio.
    attention_type: gqa | mla | rwkv | hybrid (parallel attn+mamba heads).
    input_mode: tokens | embeds (audio frontend stub) | tokens+embeds (vlm).
    """

    name: str
    family: str
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // num_heads
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False

    # --- MoE ---
    moe: bool = False
    num_experts: int = 0
    experts_per_token: int = 0
    num_shared_experts: int = 0
    moe_d_ff: int = 0  # per-expert hidden dim (0 -> d_ff)
    dense_residual: bool = False  # arctic: dense MLP in parallel with MoE
    first_dense_layers: int = 0  # deepseek-v3: leading dense layers
    router_aux_coef: float = 0.0
    # expert share (expert parallelism): this chip holds `experts_held`
    # experts, ids first_expert .. first_expert + experts_held - 1, routes
    # every token over all `num_experts` and computes, without dropping a
    # token, its own experts' part of the layer (models/moe.py). 0 = all
    # experts held, the capacity-dropping layer.
    experts_held: int = 0
    first_expert: int = 0

    # --- MLA (deepseek-v3) ---
    attention_type: str = "gqa"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_rope_dim: int = 0
    qk_nope_dim: int = 0
    v_head_dim: int = 0

    # --- SSM / hybrid ---
    ssm_state: int = 0
    rwkv_head_size: int = 64

    # --- long-context policy ---
    # serving's long-context mode; with `layer_types` also the width of
    # the "window" layers' band (a key is visible iff 0 <= q - k < width)
    sliding_window: int = 8192

    # --- per-layer attention pattern (mellum2) ---
    # one period of layer kinds, "window" or "full", repeated over the
    # depth and run as one scanned period; () = every layer full causal.
    # Window layers use plain RoPE at rope_theta; full layers YaRN when
    # yarn_factor > 0 (models/layers.yarn_inv_freq).
    layer_types: Tuple[str, ...] = ()
    yarn_factor: float = 0.0
    yarn_original_max_position: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.0
    attn_block: int = 512  # query/key block of the banded attention

    # --- multi-token prediction aux head (deepseek-v3) ---
    mtp: bool = False

    # --- modality frontend stub ---
    input_mode: str = "tokens"
    embed_prefix_len: int = 0  # vlm: number of patch-embedding tokens

    dtype: str = "bfloat16"
    remat: bool = True
    # scan_layers=False unrolls the layer stack AND the attention kv-block
    # loop into straight-line HLO — used by the dry-run cost-extrapolation
    # pass because XLA cost_analysis counts lax.scan bodies ONCE (trip
    # counts are not multiplied). Production configs keep scan=True.
    scan_layers: bool = True
    source: str = ""  # citation (hf model card / arXiv id)

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // max(self.num_heads, 1))
        if self.moe and self.moe_d_ff == 0:
            object.__setattr__(self, "moe_d_ff", self.d_ff)
        assert self.num_heads == 0 or self.num_heads % max(self.num_kv_heads, 1) == 0, (
            f"{self.name}: num_heads={self.num_heads} not divisible by "
            f"num_kv_heads={self.num_kv_heads}"
        )
        assert set(self.layer_types) <= {"window", "full"}, self.layer_types
        assert not self.layer_types or self.num_layers % len(self.layer_types) == 0, (
            f"{self.name}: {self.num_layers} layers are no whole number of "
            f"periods of {self.layer_types}"
        )
        assert self.experts_held == 0 or (
            self.first_expert + self.experts_held <= self.num_experts), (
            f"{self.name}: experts {self.first_expert}.."
            f"{self.first_expert + self.experts_held - 1} are not among "
            f"{self.num_experts}"
        )

    @property
    def local_experts(self) -> int:
        """Experts whose weights this chip holds."""
        return self.experts_held or self.num_experts

    # ---------------------------------------------------------------- reduced
    def reduced(self) -> "ModelConfig":
        """Smoke-test variant: 2 layers, d_model<=512, <=4 experts.

        Keeps the family/attention type identical so the smoke test
        exercises the same code path as the full config.
        """
        d_model = min(self.d_model, 256)
        n_heads = max(2, min(self.num_heads, 4))
        ratio = max(1, self.num_heads // max(self.num_kv_heads, 1))
        n_kv = max(1, n_heads // min(ratio, n_heads))
        changes = dict(
            name=self.name + "-reduced",
            num_layers=2,
            d_model=d_model,
            num_heads=n_heads,
            num_kv_heads=n_kv,
            head_dim=d_model // n_heads,
            d_ff=min(self.d_ff, 512),
            vocab_size=min(self.vocab_size, 512),
            sliding_window=64,
            embed_prefix_len=min(self.embed_prefix_len, 8),
        )
        if self.moe:
            changes.update(
                num_experts=min(self.num_experts, 4),
                experts_per_token=min(self.experts_per_token, 2),
                moe_d_ff=min(self.moe_d_ff, 256),
                first_dense_layers=min(self.first_dense_layers, 1),
            )
            if self.experts_held:
                changes.update(experts_held=changes["num_experts"],
                               first_expert=0)
        if self.layer_types:
            # one window and one full layer: both kinds in two layers
            changes.update(layer_types=(self.layer_types[0],
                                        self.layer_types[-1]),
                           attn_block=8)
        if self.attention_type == "mla":
            changes.update(
                q_lora_rank=min(self.q_lora_rank, 64),
                kv_lora_rank=min(self.kv_lora_rank, 32),
                qk_rope_dim=16,
                qk_nope_dim=16,
                v_head_dim=d_model // n_heads,
            )
        if self.ssm_state:
            changes.update(ssm_state=min(self.ssm_state, 8))
        return dataclasses.replace(self, **changes)

    # ------------------------------------------------------------- accounting
    def param_count(self) -> int:
        """Analytic parameter count (matches models/transformer.py init)."""
        d, L, V = self.d_model, self.num_layers, self.vocab_size
        n_emb = V * d * (1 if self.tie_embeddings else 2)
        per_layer = 0
        # attention
        if self.attention_type in ("gqa", "hybrid"):
            hd = self.head_dim
            q = d * self.num_heads * hd
            kv = 2 * d * self.num_kv_heads * hd
            o = self.num_heads * hd * d
            per_layer += q + kv + o
            if self.qkv_bias:
                per_layer += (self.num_heads + 2 * self.num_kv_heads) * hd
        elif self.attention_type == "mla":
            qr = self.q_lora_rank or d
            per_layer += d * qr + qr * self.num_heads * (self.qk_nope_dim + self.qk_rope_dim)
            per_layer += d * (self.kv_lora_rank + self.qk_rope_dim)
            per_layer += self.kv_lora_rank * self.num_heads * (self.qk_nope_dim + self.v_head_dim)
            per_layer += self.num_heads * self.v_head_dim * d
        if self.attention_type == "rwkv":
            # rwkv6 time-mix: r,k,v,g,o + decay params (approx)
            per_layer += 5 * d * d + 2 * d
        if self.attention_type == "hybrid" and self.ssm_state:
            # mamba head branch: in_proj (x,z), dt, B, C, out_proj (approx)
            per_layer += 2 * d * d + d * self.ssm_state * 2 + d * d
        # mlp
        moe_layers = L - self.first_dense_layers if self.moe else 0
        dense_layers = L - moe_layers
        dense_mlp = 3 * d * self.d_ff
        per_expert = 3 * d * self.moe_d_ff
        total = n_emb + L * per_layer + 2 * d  # final norm + per-layer norms approx
        total += dense_layers * dense_mlp
        if self.moe:
            total += moe_layers * (
                self.local_experts * per_expert
                + self.num_shared_experts * per_expert
                + d * self.num_experts  # router
                + (dense_mlp if self.dense_residual else 0)
            )
        return int(total)

    def active_param_count(self) -> int:
        """Activated params per token (MoE: only routed-in experts)."""
        if not self.moe:
            return self.param_count()
        d = self.d_model
        per_expert = 3 * d * self.moe_d_ff
        moe_layers = self.num_layers - self.first_dense_layers
        inactive = moe_layers * per_expert * (
            self.local_experts - min(self.experts_per_token, self.local_experts)
        )
        return int(self.param_count() - inactive)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


INPUT_SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    multi_pod: bool = False

    @property
    def shape(self) -> Tuple[int, ...]:
        return (2, 16, 16) if self.multi_pod else (16, 16)

    @property
    def axes(self) -> Tuple[str, ...]:
        return ("pod", "data", "model") if self.multi_pod else ("data", "model")

    @property
    def num_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


@dataclasses.dataclass(frozen=True)
class FedConfig:
    """Federated-algorithm selection + FedGiA hyper-parameters (paper §V.B)."""

    algorithm: str = "fedgia"  # fedgia | fedavg | fedprox | fedpd | scaffold
    num_clients: int = 16
    k0: int = 5  # local steps between communications
    alpha: float = 0.5  # |C| / m, client-selection fraction
    sigma_t: float = 0.15  # sigma = t * r / m (paper Table III)
    lipschitz: float = 1.0  # r (estimated online when auto_lipschitz)
    auto_lipschitz: bool = False
    h_policy: str = "diag_ema"  # diag_ema | scalar | gram (linear models only)
    collapsed: bool = True  # beyond-paper exact closed-form round (DESIGN §6 B1)
    # flat-buffer round path (engine `flat=True`): route the collapsed
    # ADMM/GD branch through the batched Pallas kernel
    # (kernels/fedgia_update). None = auto (kernel on TPU, fused jnp
    # closed form elsewhere); kernel_interpret runs the kernel in Pallas
    # interpret mode (CPU tests).
    use_kernel: Optional[bool] = None
    kernel_interpret: bool = False
    client_axes: Tuple[str, ...] = ("data",)  # mesh axes that enumerate clients
    # §Perf knobs (see EXPERIMENTS.md):
    # fsdp_axes: additionally shard client-state inner dims over these mesh
    #   axes (FSDP) — required to fit >100B-param archs with few clients.
    fsdp_axes: Tuple[str, ...] = ()
    # replicate_params: keep model params replicated over `model` and run
    #   pure data-parallel compute within the client (gradient all-reduce
    #   once per round instead of per-layer TP activation all-reduces) —
    #   the right regime for small archs where TP is overkill.
    replicate_params: bool = False
    # baseline hyper-parameters (paper §V.D)
    lr: float = 0.01
    prox_mu: float = 1e-4
    inner_steps: int = 5  # FedProx/FedPD inner GD steps
    fedpd_eta: float = 1.0
    state_dtype: str = "float32"


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    steps: int = 100  # communication rounds
    seed: int = 0
    log_every: int = 10
    checkpoint_every: int = 0
    checkpoint_dir: str = ""
    tol: float = 0.0  # grad-norm^2 stopping tolerance (paper eq. 35); 0 = off


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    long_context: bool = False  # sliding-window ring-buffer KV cache
    max_cache_len: int = 32_768
    decode_dtype: str = "bfloat16"
