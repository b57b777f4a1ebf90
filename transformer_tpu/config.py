"""Configuration for models, training, and device meshes.

The reference keeps its entire configuration as a flat absl-flags namespace of
15 knobs (reference ``utils.py:17-33`` plus ``distributed_train.py:23``). Here
the same capability surface is three frozen dataclasses — model / training /
mesh — so configs are hashable (usable as jit static args), serializable, and
composable. The CLI layer (``transformer_tpu/cli``) still exposes the
reference's flag names for drop-in familiarity.
"""

from __future__ import annotations

import dataclasses
import json
from typing import TYPE_CHECKING, Any, Mapping

if TYPE_CHECKING:
    import jax.numpy as jnp

# Special-token convention, matching the reference pipeline (``utils.py:137-143``):
# pad = 0; BOS = subword_vocab_size; EOS = subword_vocab_size + 1, so a model's
# embedding table has subword_vocab_size + 2 rows (reference ``train.py:232-233``).
PAD_ID = 0


@dataclasses.dataclass(frozen=True)
class AttentionKind:
    """One kind of layer in a model whose layers differ
    (``ModelConfig.attention_kinds`` / ``layer_pattern``): a self-attention
    layer's query heads, its causal window and its rotary frequencies (every
    attention kind shares the model's KV heads and head size, so all of them
    fit one KV pool), or one of three other mixers in the attention
    sublayer's place (``mixer`` says which): with ``conv_kernel``, a gated
    short convolution (``ops/short_conv.py``), whose state is
    ``conv_kernel - 1`` rows a sequence and no KV rows at all; with
    ``kda_heads``, delta-rule linear attention (``ops/kda.py``), whose state
    is a ``kda_head_dim`` x ``kda_head_dim`` float32 matrix a head and the
    last ``kda_conv_kernel - 1`` inputs of three short convolutions; with
    ``latent_rank``, latent attention (``ops/mla.py``), which keeps ONE row
    of ``latent_rank + latent_shared_dim`` channels a position that every
    head reads, in a pool entry of its own."""

    name: str
    num_heads: int = 0  # query heads; 0 = ModelConfig.num_heads
    # Causal band over POSITIONAL storage (a full-length cache or the paged
    # pool, masked at absolute positions) -- unlike the model-wide
    # ``ModelConfig.attention_window``, which also makes the dense cache roll.
    window: int = 0
    rope_base: float = 10000.0
    # Share of the head that is rotated (its first channels); the rest passes.
    rotary_share: float = 1.0
    # YaRN (arXiv:2309.00071) frequency blend; factor 0 = plain rotary.
    yarn_factor: float = 0.0
    yarn_original_max_position: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    # Multiplies cos and sin (YaRN's attention factor; 1 = none).
    rope_attention_factor: float = 1.0
    # Taps of the causal depthwise convolution of a short-convolution layer
    # (0 = an attention layer; the fields above then say which).
    conv_kernel: int = 0
    # Delta-rule linear attention (KDA): heads, the width of a head's keys
    # and of its values, the taps of the causal depthwise convolutions on q,
    # k and v, and the rank of the decay gate and of the output gate (0 = the
    # head's width). 0 heads = not such a layer.
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv_kernel: int = 4
    kda_gate_rank: int = 0
    # Latent attention (MLA): the latent's rank, a head's key part made from
    # the latent, the key part all heads share (no rotation is applied), and
    # a head's value width. Rank 0 = not such a layer.
    latent_rank: int = 0
    latent_nope_dim: int = 0
    latent_shared_dim: int = 0
    latent_value_dim: int = 0
    # Seeded (not loaded) weights only, as ``ModelConfig.moe_router_init_scale``:
    # a factor on the Glorot draw of a latent layer's query kernel. A trained
    # attention is peaked; a Glorot draw's scores are so flat that over
    # thousands of positions the values average away and nothing the scores
    # are made of shows in the layer's output.
    latent_query_init_scale: float = 1.0

    @property
    def mixer(self) -> str:
        """What stands in the attention sublayer: ``"attention"``,
        ``"conv"``, ``"kda"`` or ``"mla"``."""
        if self.conv_kernel:
            return "conv"
        if self.kda_heads:
            return "kda"
        return "mla" if self.latent_rank else "attention"


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture of one Transformer (encoder-decoder or decoder-only).

    Defaults mirror the reference flag defaults (``utils.py:18-33``):
    4 layers, d_model=512, dff=1024, 4 heads, dropout 0.1.
    """

    num_layers: int = 4
    d_model: int = 512
    num_heads: int = 4
    # Grouped-query / multi-query attention (Shazeer 2019): k/v carry this
    # many heads, each serving num_heads/num_kv_heads query heads — the
    # decode KV cache (and kv parameter count) shrinks by that factor.
    # 0 = num_heads (standard MHA, the reference's attention).
    num_kv_heads: int = 0
    # Size of one head where it is not d_model / num_heads (0 = that).
    head_size: int = 0
    dff: int = 1024
    input_vocab_size: int = 32000
    target_vocab_size: int = 32000
    dropout_rate: float = 0.1
    # Positional table sized by max positions — deliberately fixing the
    # reference's vocab-sized table (SURVEY.md §2.3.5; reference ``Encoder.py:40``).
    max_position: int = 4096
    # Post-LN matches the reference residual wiring (``Encoder.py:19-29``);
    # "pre" is offered because pre-LN is markedly more stable at depth.
    norm_scheme: str = "post"  # "post" | "pre"
    # Position encoding: "sinusoidal" = the reference's additive table
    # (``positionalencoding.py:8-23``); "rope" = rotary embeddings applied to
    # q/k in self-attention (``ops/positional.py apply_rope``) — the
    # long-context extension (relative positions, no additive table).
    position_scheme: str = "sinusoidal"  # "sinusoidal" | "rope"
    layernorm_epsilon: float = 1e-6
    # BASELINE.json configs[3]: tied src/tgt embeddings and tied output projection.
    tie_embeddings: bool = False  # share encoder/decoder embedding tables
    tie_output: bool = False  # logits = h @ embedding.T instead of a fresh Dense
    # BASELINE.json configs[4]: decoder-only causal LM (no encoder, no cross-attn).
    decoder_only: bool = False
    # Encoder-only bidirectional model (BERT family): the encoder stack with
    # padding masks only, plus the vocab head — trained with the masked-LM
    # objective (TrainConfig.objective="mlm"). No reference counterpart (the
    # reference is translation-only); completes the encoder / decoder /
    # encoder-decoder family triad.
    encoder_only: bool = False
    # Activation in the pointwise FFN; reference uses relu (``point_ffn.py:5``).
    # swiglu/geglu/reglu are the gated three-matmul variants (Shazeer 2020) —
    # the modern-LLM FFN (dense layers only; MoE experts stay ungated).
    ffn_activation: str = "relu"  # relu | gelu | silu | swiglu | geglu | reglu
    # Compute dtype: bf16 keeps the MXU fed at full rate; params stay fp32.
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    # Attention implementation: "xla" (einsum softmax einsum, XLA-fused),
    # "flash" (Pallas blockwise kernel), "ring" (sequence-parallel ring over
    # ICI), "ulysses" (sequence-parallel head all-to-all). ring/ulysses train
    # through DistributedTrainer with MeshConfig(seq>1).
    attention_impl: str = "xla"
    # Block sizes for the Pallas flash-attention kernel.
    flash_block_q: int = 128
    flash_block_k: int = 128
    # Rematerialize each layer's activations in the backward pass
    # (jax.checkpoint): trades ~1/3 more FLOPs for O(layers) less activation
    # HBM — the standard lever for long-context configs (BASELINE configs[4]).
    remat: bool = False
    # What remat may KEEP from the forward pass ("full" = keep nothing,
    # recompute everything — minimum memory, ~1/3 extra FLOPs; "dots" =
    # jax.checkpoint_policies.dots_with_no_batch_dims_saveable: save matmul
    # outputs, recompute only the cheap elementwise/bandwidth-bound ops —
    # most of the memory win at a fraction of the recompute, usually the
    # better point on TPUs where MXU FLOPs are the scarce resource).
    remat_policy: str = "full"  # "full" | "dots"
    # Sliding-window (local) attention for CAUSAL self-attention: each
    # position attends only the last `attention_window` positions
    # (Mistral-style). Applies to decoder self-attention and decoder-only
    # LMs; encoder self-attention and cross-attention are unaffected.
    # Structural in the flash kernel (out-of-band tiles skipped: per-row
    # compute O(window), not O(S)); banded mask under xla; rolling O(window)
    # KV cache at decode; under ring sequence parallelism out-of-band hops
    # stop the ring early (ICI traffic O(window)); ulysses applies the band
    # in its per-device flash call. 0 = full attention.
    attention_window: int = 0
    # int8 decode KV cache (ops/attention.py init_cache(quantize=True)):
    # k/v stored int8 with one fp32 scale per (position, head) row,
    # dequantized on read — ~2x (vs bf16) to ~4x (vs fp32) less HBM for the
    # long-context serving bottleneck. Decode-only; training is unaffected.
    kv_cache_int8: bool = False
    # Mixture-of-Experts FFN (capability extension; the reference's FFN is
    # dense, ``point_ffn.py:3-7``). 0 = dense FFN everywhere. When > 0, every
    # ``moe_every``-th layer replaces its FFN with a ``moe_experts``-expert
    # MoE (``ops/moe.py``), sharded over the mesh's ``expert`` axis.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_every: int = 1  # 1 = every layer; 2 = every other layer (GShard style)
    moe_aux_weight: float = 0.01  # load-balance loss weight in the objective
    # "capacity": the fixed-slot dispatch above, which drops overflow tokens
    # (training). "dropless": tokens grouped by expert and a grouped product
    # over the experts that received any (``ops/moe.py moe_apply_dropless``);
    # gated experts without biases, a shared expert, and this chip's share of
    # an expert-parallel layer exist only there.
    moe_dispatch: str = "capacity"  # "capacity" | "dropless"
    moe_dff: int = 0  # width of one routed expert; 0 = dff
    moe_shared_dff: int = 0  # width of the shared expert every token takes; 0 = none
    # Experts held HERE out of the router's ``moe_experts`` (0 = all), and the
    # id of the first: picks that fall on other chips' experts add nothing.
    moe_experts_held: int = 0
    moe_expert_offset: int = 0
    moe_routed_scale: float = 1.0  # multiplies the renormalised top-k weights
    moe_leading_dense: int = 0  # leading layers that keep the dense FFN
    # Seeded (not loaded) weights only: factors on the Glorot draw of the
    # router's kernel and of the routed experts' out kernels. A trained
    # router is peaked (a token's first pick carries most of its weight) and
    # a trained branch is small beside the residual stream; a Glorot draw is
    # flat and large, and two precisions of one model then part ways at every
    # near-tie of the last pick and the next. Stand-in weights that should
    # behave like a checkpoint's set these (PERF.md section 6, PR 28).
    moe_router_init_scale: float = 1.0
    moe_out_init_scale: float = 1.0
    # The dropless router's scores: "softmax" over all experts, the top-k
    # renormalised; or "sigmoid" (the auxiliary-loss-free balancing of
    # arXiv:2408.15664): the top-k of ``score + bias`` where
    # ``moe_select_bias`` gives the router a float32 bias that takes part in
    # the choice only, weights ``score / (sum of the chosen scores +
    # moe_renorm_epsilon)`` from the scores without it.
    moe_score: str = "softmax"  # "softmax" | "sigmoid"
    moe_select_bias: bool = False
    moe_renorm_epsilon: float = 1e-6
    # RMSNorm over each head's channels of q and of k (own scales), before
    # the rotation; its epsilon is ``layernorm_epsilon``.
    qk_norm: bool = False
    # Block options of the RMSNorm / no-bias families.
    norm: str = "layernorm"  # "layernorm" | "rmsnorm" (parameter: scale only)
    use_bias: bool = True  # biases on projections, FFN and the untied head
    attention_gate: str = ""  # "per_head": sigmoid gate on each head's output
    # Layers of several kinds: layer i is of kind layer_pattern[i % period],
    # a name in ``attention_kinds``. Empty = every layer alike (num_heads,
    # attention_window, rotary base 10,000 over the whole head). JSON lists
    # and dicts are accepted and stored as tuples, so the config stays a
    # hashable static argument.
    layer_pattern: tuple[str, ...] = ()
    attention_kinds: tuple[AttentionKind, ...] = ()

    def __post_init__(self) -> None:
        object.__setattr__(self, "layer_pattern", tuple(self.layer_pattern))
        object.__setattr__(self, "attention_kinds", tuple(
            k if isinstance(k, AttentionKind) else AttentionKind(**k)
            for k in self.attention_kinds
        ))
        names = [k.name for k in self.attention_kinds]
        if len(set(names)) != len(names) or set(self.layer_pattern) - set(names):
            raise ValueError(
                f"layer_pattern {self.layer_pattern} must name attention_kinds "
                f"(distinct names; got {names})"
            )
        if bool(self.layer_pattern) != bool(self.attention_kinds):
            raise ValueError("layer_pattern and attention_kinds come together")
        if self.layer_pattern and not self.decoder_only:
            raise ValueError("attention kinds (causal windows, heads by layer) are a decoder-only model's")
        for k in self.attention_kinds:
            heads = k.num_heads or self.num_heads
            if heads % self.kv_heads or k.window < 0 or not 0.0 < k.rotary_share <= 1.0:
                raise ValueError(f"attention kind {k} does not fit this model")
            if int(self.head_dim * k.rotary_share) % 2:
                raise ValueError(f"attention kind {k.name!r} rotates an odd number of channels")
            if k.conv_kernel < 0 or k.conv_kernel == 1:
                raise ValueError(f"layer kind {k.name!r}: conv_kernel is 0 (attention) or 2 and more taps")
            if sum(map(bool, (k.conv_kernel, k.kda_heads, k.latent_rank))) > 1:
                raise ValueError(f"layer kind {k.name!r} names more than one mixer")
            if k.kda_heads and (k.kda_head_dim < 1 or k.kda_conv_kernel < 2 or k.kda_gate_rank < 0):
                raise ValueError(
                    f"layer kind {k.name!r}: a delta-rule layer needs kda_head_dim "
                    "and 2 and more taps"
                )
            if k.latent_rank and min(k.latent_nope_dim, k.latent_shared_dim, k.latent_value_dim) < 1:
                raise ValueError(
                    f"layer kind {k.name!r}: a latent layer needs latent_nope_dim, "
                    "latent_shared_dim and latent_value_dim"
                )
            if k.latent_query_init_scale <= 0:
                raise ValueError(f"layer kind {k.name!r}: latent_query_init_scale must be > 0")
        if self.moe_score not in ("softmax", "sigmoid"):
            raise ValueError(f"moe_score must be 'softmax' or 'sigmoid', got {self.moe_score!r}")
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"norm must be 'layernorm' or 'rmsnorm', got {self.norm!r}")
        if self.attention_gate not in ("", "per_head"):
            raise ValueError(f"attention_gate must be '' or 'per_head', got {self.attention_gate!r}")
        if self.moe_dispatch not in ("capacity", "dropless"):
            raise ValueError(f"moe_dispatch must be 'capacity' or 'dropless', got {self.moe_dispatch!r}")
        if self.head_size < 0 or (not self.head_size and self.d_model % self.num_heads != 0):
            # Same invariant the reference asserts (``Attention.py:42``).
            raise ValueError(
                f"d_model ({self.d_model}) must be divisible by num_heads "
                f"({self.num_heads})"
            )
        if self.encoder_only and self.decoder_only:
            raise ValueError(
                "encoder_only and decoder_only are mutually exclusive"
            )
        if self.encoder_only and self.input_vocab_size != self.target_vocab_size:
            # One tower, one id space: the MLM [MASK] id is
            # input_vocab_size - 1 while the head/loss are sized by
            # target_vocab_size — a mismatch would silently clamp labels.
            raise ValueError(
                "encoder_only models use one id space: input_vocab_size "
                f"({self.input_vocab_size}) must equal target_vocab_size "
                f"({self.target_vocab_size})"
            )
        if self.norm_scheme not in ("post", "pre"):
            raise ValueError(f"norm_scheme must be 'post' or 'pre', got {self.norm_scheme!r}")
        if self.remat_policy not in ("full", "dots"):
            raise ValueError(
                f"remat_policy must be 'full' or 'dots', got {self.remat_policy!r}"
            )
        if self.attention_window < 0:
            raise ValueError(
                f"attention_window must be >= 0, got {self.attention_window}"
            )
        if self.position_scheme not in ("sinusoidal", "rope"):
            raise ValueError(
                f"position_scheme must be 'sinusoidal' or 'rope', got "
                f"{self.position_scheme!r}"
            )
        if self.position_scheme == "rope" and self.head_dim % 2:
            raise ValueError(
                "position_scheme='rope' needs an even head_dim "
                f"(got {self.head_dim})"
            )
        # Single source of truth for activation names: the op registry.
        from transformer_tpu.ops.ffn import FFN_ACTIVATIONS, is_gated

        if self.ffn_activation not in FFN_ACTIVATIONS:
            raise ValueError(f"unknown ffn_activation {self.ffn_activation!r}")
        dropless = self.moe_dispatch == "dropless"
        # Nothing in the mathematics ties the expert's form to the dispatch:
        # each dispatch computes the one form its user has (the capacity
        # einsums the reference's biased ungated FFN, the grouped kernel
        # ``moe_expert_ffn`` three bias-free matrices), so the other two
        # pairs are refused here rather than computed as something else.
        if self.moe_experts and is_gated(self.ffn_activation) != dropless:
            raise ValueError(
                "capacity-dispatch experts are ungated and dropless experts "
                f"gated: got ffn_activation={self.ffn_activation!r} with "
                f"moe_dispatch={self.moe_dispatch!r}"
            )
        held = self.experts_held
        if not dropless and (
            self.moe_experts_held or self.moe_expert_offset or self.moe_shared_dff
            or self.moe_dff or self.moe_routed_scale != 1.0
            or self.moe_score != "softmax" or self.moe_select_bias
        ):
            raise ValueError(
                "a share of the experts, a shared expert, an expert width, a "
                "routed scale, sigmoid scores and a selection bias need "
                "moe_dispatch='dropless'"
            )
        if self.moe_select_bias and self.moe_score != "sigmoid":
            raise ValueError("moe_select_bias goes with moe_score='sigmoid'")
        if self.moe_router_init_scale <= 0 or self.moe_out_init_scale <= 0:
            raise ValueError(
                "moe_router_init_scale and moe_out_init_scale must be > 0 (got "
                f"{self.moe_router_init_scale}/{self.moe_out_init_scale})"
            )
        if self.moe_expert_offset < 0 or self.moe_expert_offset + held > self.moe_experts:
            raise ValueError(
                f"experts {self.moe_expert_offset}..{self.moe_expert_offset + held} "
                f"held here lie outside the router's {self.moe_experts}"
            )
        if self.attention_impl not in ("xla", "flash", "ring", "ulysses"):
            raise ValueError(f"unknown attention_impl {self.attention_impl!r}")
        if self.moe_experts < 0 or self.moe_top_k < 1 or self.moe_every < 1:
            raise ValueError(
                "moe_experts must be >= 0, moe_top_k and moe_every >= 1 "
                f"(got {self.moe_experts}/{self.moe_top_k}/{self.moe_every})"
            )
        if self.moe_experts and self.moe_top_k > self.moe_experts:
            raise ValueError(
                f"moe_top_k ({self.moe_top_k}) cannot exceed moe_experts "
                f"({self.moe_experts})"
            )
        if self.num_kv_heads < 0 or self.num_kv_heads > self.num_heads or (
            self.num_kv_heads and self.num_heads % self.num_kv_heads
        ):
            raise ValueError(
                f"num_kv_heads ({self.num_kv_heads}) must be 0 (= num_heads) "
                f"or a positive divisor of num_heads ({self.num_heads})"
            )

    @property
    def head_dim(self) -> int:
        return self.head_size or self.d_model // self.num_heads

    def layer_kind(self, layer_index: int) -> AttentionKind:
        """The attention kind of layer ``layer_index``; for a model whose
        layers are all alike, the one kind its scalar fields describe."""
        if not self.layer_pattern:
            return AttentionKind("", self.num_heads, self.attention_window)
        name = self.layer_pattern[layer_index % len(self.layer_pattern)]
        kind = next(k for k in self.attention_kinds if k.name == name)
        return kind if kind.num_heads else dataclasses.replace(kind, num_heads=self.num_heads)

    @property
    def state_layers(self) -> tuple[int, ...]:
        """Layers whose state a sequence carries is not rows a position: the
        short-convolution and the delta-rule layers (a latent layer keeps a
        row a position and is not one)."""
        return tuple(
            i for i in range(self.num_layers)
            if self.layer_kind(i).mixer in ("conv", "kda")
        )

    @property
    def experts_held(self) -> int:
        return self.moe_experts_held or self.moe_experts

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def compute_dtype(self) -> jnp.dtype:
        # jax is imported here, not at module top: the config dataclasses are
        # read by processes that must stay off JAX (the router parent).
        import jax.numpy as jnp

        return jnp.dtype(self.dtype)

    @property
    def params_dtype(self) -> jnp.dtype:
        import jax.numpy as jnp

        return jnp.dtype(self.param_dtype)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Training-engine knobs; defaults mirror the reference (``utils.py:18-33``,
    ``train.py:21-22,65-66``)."""

    batch_size: int = 64
    sequence_length: int = 50
    epochs: int = 4
    # Noam schedule warmup. The reference defaults to 60000 (``train.py:22``),
    # not the paper's 4000 — kept as the default for parity.
    warmup_steps: int = 60000
    # LR schedule family (train/schedule.py): "noam" is the reference's
    # CustomSchedule; "cosine"/"constant" warm up linearly to ``peak_lr``
    # (required > 0 for those), cosine decaying to peak_lr/10 at
    # ``lr_decay_steps`` (required for cosine).
    lr_schedule: str = "noam"  # "noam" | "cosine" | "constant"
    peak_lr: float = 0.0
    lr_decay_steps: int = 0
    adam_beta1: float = 0.9
    adam_beta2: float = 0.98
    adam_epsilon: float = 1e-9
    # "adam": the reference's optimizer exactly (``train.py:65-66``).
    # "adafactor": factored second moments — O(d_in + d_out) optimizer state
    # per matrix instead of Adam's 2x params, the standard memory lever for
    # big-model training.
    # "adamw": decoupled weight decay (``weight_decay``) on matrices only
    # (vectors — biases, layernorms — are exempt).
    optimizer: str = "adam"  # "adam" | "adafactor" | "adamw"
    weight_decay: float = 0.0  # adamw only
    label_smoothing: float = 0.0  # BASELINE.json configs[2] uses > 0
    # "tokens": mean CE over non-pad tokens (the sane default).
    # "batch": sum of per-token CE divided by global batch size — the
    # reference's exact normalization (``train.py:83-88``), offered for parity.
    loss_normalization: str = "tokens"
    max_grad_norm: float = 0.0  # 0 disables clipping (reference has none)
    buffer_size: int = 100000  # shuffle buffer (reference ``utils.py:19``)
    eval_every_steps: int = 500
    # In-loop eval batch cap: the reference either runs the FULL test set
    # every 100 steps (``train.py:193-195``) or ~1 batch (``distributed_
    # train.py:94``) — both defects (SURVEY §2.3.3/.6). Bounded and
    # configurable here; 0 = no cap (full test set).
    eval_max_batches: int = 8
    # Early stopping: stop after this many consecutive epochs without
    # end-of-epoch eval-loss improvement (0 = off; needs a test dataset).
    # The reference always runs all epochs (``train.py:180``).
    early_stop_patience: int = 0
    log_every_steps: int = 100
    checkpoint_every_epochs: int = 5  # intent of the reference's (buggy) save cond
    max_ckpt_keep: int = 5
    ckpt_path: str = "model_dist"
    enable_function: bool = True  # jit on/off — the reference's eager-debug flag
    seed: int = 0
    # GPipe microbatches per step when the mesh has a pipe axis; 0 = one
    # microbatch per stage (parallel/pipeline.py).
    pp_microbatches: int = 0
    # Pipeline schedule: "gpipe" (forward schedule + autodiff backward,
    # activation stash grows with pp_microbatches) or "1f1b" (manual
    # interleaved forward/backward schedule, stash bounded at 2*stages-1
    # microbatches regardless of pp_microbatches — the pod-scale memory
    # profile). 1f1b supports dense models (decoder-only and seq2seq —
    # the seq2seq decoder stack runs the engine, the encoder half GPipe)
    # on data x fsdp x model x pipe meshes (parallel/pipeline.py
    # pipeline_train_1f1b).
    pp_schedule: str = "gpipe"
    # Gradient accumulation: split each batch into this many sequential
    # micro-steps and sum gradients before one optimizer update — train
    # big-model global batches on small-HBM chips. 1 = off.
    grad_accum_steps: int = 1
    # Chunked loss: compute the final vocab projection + CE over this many
    # sequence slices (train/loss.py chunked_cross_entropy_from_hidden) so
    # the full (B, S, V) logits tensor is never materialized — the memory
    # lever for big-vocab/long-context configs. 1 = off.
    loss_chunks: int = 1
    # Host-dispatch amortization: run this many optimizer steps inside ONE
    # jitted lax.scan per host→device dispatch (trainer.py
    # make_multistep_train_step). At small step times the per-step Python/
    # runtime dispatch is a share of wall clock; K steps per dispatch divide
    # it by K (never measured on the chip). Orthogonal
    # to grad_accum_steps (each inner step is still a full optimizer
    # update). Trade-off: preemption/log/eval granularity becomes K steps.
    # 1 = off.
    steps_per_dispatch: int = 1
    # Training objective: "causal" (teacher-forcing shift — seq2seq and
    # decoder-only LM) or "mlm" (BERT-style dynamic masked-LM for
    # ModelConfig.encoder_only: 15% of non-pad positions selected per step,
    # 80% [MASK] / 10% random / 10% kept; loss only on selected positions).
    # The [MASK] id is the model's top input id (input_vocab_size - 1) —
    # size the vocab one larger than the tokenizer's (train/mlm.py).
    objective: str = "causal"
    mlm_mask_rate: float = 0.15
    # Special ids excluded from MLM selection AND from the 10% random-
    # replacement draw (BERT/RoBERTa exclude specials from both). None =
    # auto: the framework's vocab layout puts BOS/EOS at the two ids
    # directly below [MASK] (tokenizer bos=vocab_size, eos=vocab_size+1,
    # mask=model_vocab+1-1 — see cli/flags.py MLM sizing), so auto excludes
    # (mask_id-2, mask_id-1). Pass () to exclude nothing (custom layouts).
    mlm_excluded_ids: tuple[int, ...] | None = None

    def __post_init__(self) -> None:
        if self.loss_normalization not in ("tokens", "batch"):
            raise ValueError(
                f"loss_normalization must be 'tokens' or 'batch', got {self.loss_normalization!r}"
            )
        if self.objective not in ("causal", "mlm"):
            raise ValueError(
                f"objective must be 'causal' or 'mlm', got {self.objective!r}"
            )
        if not 0.0 < self.mlm_mask_rate < 1.0:
            raise ValueError(
                f"mlm_mask_rate must be in (0, 1), got {self.mlm_mask_rate}"
            )
        if self.pp_schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                f"pp_schedule must be 'gpipe' or '1f1b', got {self.pp_schedule!r}"
            )
        if self.optimizer not in ("adam", "adafactor", "adamw"):
            raise ValueError(
                "optimizer must be 'adam', 'adafactor' or 'adamw', got "
                f"{self.optimizer!r}"
            )
        if self.weight_decay and self.optimizer != "adamw":
            raise ValueError(
                "weight_decay > 0 requires optimizer='adamw' (adam/adafactor "
                "would silently ignore it)"
            )
        if self.lr_schedule not in ("noam", "cosine", "constant"):
            raise ValueError(
                f"lr_schedule must be noam/cosine/constant, got {self.lr_schedule!r}"
            )
        if self.lr_schedule != "noam" and self.peak_lr <= 0:
            raise ValueError(
                f"lr_schedule={self.lr_schedule!r} needs peak_lr > 0"
            )
        if self.lr_schedule == "cosine" and self.lr_decay_steps <= self.warmup_steps:
            raise ValueError(
                "lr_schedule='cosine' needs lr_decay_steps > warmup_steps "
                f"(got {self.lr_decay_steps} <= {self.warmup_steps})"
            )
        if self.steps_per_dispatch < 1:
            raise ValueError(
                f"steps_per_dispatch must be >= 1, got {self.steps_per_dispatch}"
            )


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Logical device mesh. Axis names are the framework-wide vocabulary used
    by every PartitionSpec:

    - ``data``: data parallelism (gradient psum over ICI — the TPU-native
      replacement for the reference's NCCL all-reduce, ``distributed_train.py:58-62``)
    - ``fsdp``: parameter/optimizer sharding (zero-style), rides the data axis
    - ``model``: tensor parallelism (attention heads / dff)
    - ``seq``: sequence/context parallelism (ring attention over ICI)
    - ``pipe``: pipeline parallelism (GPipe microbatch schedule, activations
      ppermute between stages — ``parallel/pipeline.py``). Memory note: the
      pipe axis partitions *compute*; combine with ``fsdp`` to also shard
      stage parameters/optimizer state, otherwise each device holds a full
      replica of the stacked layer params.
    - ``expert``: expert parallelism (MoE expert weights sharded over ICI,
      token slots all-to-all'd to their experts by GSPMD — ``ops/moe.py``).
    """

    data: int = 1
    fsdp: int = 1
    model: int = 1
    seq: int = 1
    pipe: int = 1
    expert: int = 1
    # Multi-slice: how many DCN-connected slices (or processes, off-TPU) the
    # DATA axis spans. Must divide ``data``. The mesh is then built hybrid
    # (jax mesh_utils): the slow inter-slice DCN hops carry only the
    # data-parallel gradient all-reduce, while fsdp/model/seq/pipe/expert
    # collectives stay on intra-slice ICI — the "collectives ride ICI, not
    # DCN" layout. 1 = single slice (plain mesh).
    dcn_data: int = 1

    @property
    def num_devices(self) -> int:
        return self.data * self.fsdp * self.model * self.seq * self.pipe * self.expert

    @property
    def axis_names(self) -> tuple[str, ...]:
        return ("data", "fsdp", "model", "seq", "pipe", "expert")

    @property
    def axis_sizes(self) -> tuple[int, ...]:
        return (self.data, self.fsdp, self.model, self.seq, self.pipe, self.expert)


def config_to_json(cfg: Any) -> str:
    """Serialize any of the config dataclasses to JSON (for export/checkpoints)."""
    return json.dumps(dataclasses.asdict(cfg), indent=2, sort_keys=True)


def config_from_json(cls: type, payload: str | Mapping[str, Any]):
    data = json.loads(payload) if isinstance(payload, str) else dict(payload)
    known = {f.name for f in dataclasses.fields(cls)}
    return cls(**{k: v for k, v in data.items() if k in known})
