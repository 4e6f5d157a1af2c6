"""Config system: ModelParameter / BlockConfig / BlockArgs.

Parses the exact JSON schema of the reference's configs/*.json
(/root/reference/src/dataclass.py:34-341) so existing configs launch
unchanged, and derives the TPU-native execution plan from it:

- mesh axes ('data', 'model'[, 'sequence']) replacing the auto-derived mtf
  mesh_shape "b:<tpu_size/heads>,h:<heads>" + layout "batch:b,heads:h"
  (/root/reference/src/dataclass.py:247-252),
- named Dims (core.dims.Dim) replacing mtf.Dimensions (:273-316),
- jnp dtypes for the storage/slice/calculation triple (:253-255).

New (TPU-first) keys, all defaulted so reference configs are unaffected:
``sequence_parallel`` (shard the sequence dim over a mesh axis for
long-context ring attention), ``mesh_shape_override``, ``scan_layers``.
"""
from __future__ import annotations

import typing

import jax.numpy as jnp
import numpy as np

from .core.dims import Dim

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
           "float16": jnp.float16, "float64": jnp.float32}
# int8 is only valid for decode_cache_dtype (KV caches store per-row-
# quantized int8 + f32 scales; model/decode.py) — the float keys above
# would fail later and obscurely (e.g. integer param init)
_CACHE_DTYPES = {**_DTYPES, "int8": jnp.int8}


class BlockConfig:
    """One block part: list of layer strings + skip flag (reference :12-19).
    ``merge`` says how a ``skip`` part joins the stream: ``"add"`` is ``x +
    f(x)``, ``"scaled"`` ZAYA1's ``(x * a_r + b_r) + (f(x) * a_o + b_o)``
    with four learned vectors over the features (model/frontend.py
    ``scaled_merge``)."""

    MERGES = ("add", "scaled")

    def __init__(self, config, memory_reduction_strategy: str):
        if isinstance(config, BlockConfig):
            config = config.__dict__
        self.layer: typing.List[str] = []
        self.skip = False
        self.merge = "add"
        self.memory_reduction_strategy = memory_reduction_strategy
        self.__dict__.update(config)
        if self.merge not in self.MERGES:
            raise ValueError(f"block part merge {self.merge!r}: one of "
                             f"{self.MERGES}")
        if self.merge != "add" and not self.skip:
            raise ValueError(f"block part merge {self.merge!r} without skip")


class LearningRateConfig:
    def __init__(self, start_step: int = 0, final_step: int = 0, factor: float = 1.):
        self.start_step = start_step
        self.final_step = final_step
        self.factor = factor


class ModelParameter:
    def __init__(self, config: typing.Dict[str, typing.Any],
                 **overrides: typing.Any):
        if isinstance(config, ModelParameter):
            config = dict(config._raw_config)
        config = {**config, **overrides}
        self._raw_config = dict(config)

        # ---- defaults: key-for-key with /root/reference/src/dataclass.py:38-179
        self.position_embedding = "absolute"
        self.token_embedding = "absolute"
        self.empty_frame_embedding = "absolute"
        self.output_embedding = "absolute-orthogonal"
        self.use_video = True
        self.save_graph = False
        self.use_language = True
        self.contrastive_across_samples = False
        self.contrastive_across_token_embeddings = False
        self.input_dropout = 0.
        self.output_offset = 1
        self.weight_standardisation = True
        self.use_checkpointing = False
        self.max_checkpoints_keep = 1
        self.steps_per_checkpoint = 100_000
        self.time_patch = 1
        self.patch_size = 16
        self.frame_width = 320
        self.frame_height = 176
        self.opt_beta1 = 0.9
        self.opt_beta2 = 0.999
        self.vocab_size = 256
        self.color_channels = 3
        self.three_axes = True
        self.dataset_configs: typing.List[dict] = []
        self.data_seed = 456772
        self.parallel_batch = None
        self.parallel_interleave = None
        self.use_random_dataloader = False
        self.train = True
        self.debug_sample = False
        self.padding_token = 0
        self.concat_token = 4
        self.sequence_length = 32
        self.heads = 8
        self.features: typing.Optional[int] = None
        self.features_per_head: typing.Optional[int] = None
        self.depth = 16
        self.buffer_size = 4
        self.combine_assignments = False
        self.shuffle_buffer = 256
        self.interleaved_datasets = 256
        self.token_patch_size = 1
        self.learning_rate = 5e-5
        self.storage_dtype = "float32"
        self.slice_dtype = "float32"
        self.calculation_dtype = "float32"
        # storage dtype for decode-time KV caches (None = calculation dtype);
        # the cache dominates decode HBM at wide batch — see BASELINE.md
        self.decode_cache_dtype = None
        # decode loop structure (infer/sampler.py).  "fused": the whole
        # generation is ONE jitted lax.while_loop (lowest dispatch overhead;
        # the cache carry's in-place aliasing is at XLA's discretion and
        # measurably breaks at multi-GB caches — BASELINE.md round 5: 60.1
        # ms/token at 32k vs the ~8 ms read bound).  "stepped": generation is
        # a host loop over a jitted CHUNK of decode steps whose carry
        # (token_x, caches, rng, position) is DONATED — input_output_aliases
        # then pins every cache update in place, a property asserted on the
        # compiled HLO (analysis/hlo_lint.py).  "auto": stepped when the cache
        # pytree exceeds decode_stepped_min_cache_gb, fused below it.
        self.decode_loop = "auto"
        # tokens per jitted chunk dispatch on the stepped path; amortises
        # per-dispatch host latency (at ~0.1 ms dispatch and >= 1 ms/token
        # big-cache steps even 16 is < 1% overhead)
        self.decode_chunk_tokens = 64
        # "auto" switches to the stepped loop at this cache size: below it
        # the fused while_loop aliases fine (measured at 0.5 GB flagship
        # scale) and avoids per-chunk dispatch entirely
        self.decode_stepped_min_cache_gb = 1.0
        self.optimizer_slice_dtype = "float32"
        self.optimizer_calculation_dtype = "float32"
        self.learning_rate_config: typing.Dict[str, typing.Any] = {}
        self.train_batch_size = 1
        self.grad_accumulation = 1
        self.macro_batching = 1
        self.macro_batch_loss_smoothing = False
        self.reduce_lr_on_plateau_timespan = 0
        self.reduce_lr_on_plateau_reduction = 2
        self.momentumnet_alpha = 0.99
        self.current_step = 0
        self.tpu_size = 32
        self.default_sleep_duration = 0.1
        self.lookahead_steps = 0
        self.lookahead_alpha = 0
        self.momentum = 0.95
        self.prefix = "datasets/full_hd_video"
        self.model_path = "runs/default"
        self.tensorflow_optimization_settings = {}  # accepted, ignored (TF1-only)
        self.language_token_per_frame = 0
        self.weight_decay = 0.001
        self.vocab_weight_factorization = 0.125
        self.train_steps = 2 ** 30
        self.warmup_steps = 3000
        self.rezero_lr_multiplier = 0.1
        self.learning_rate_decay_multi = 1
        self.convolution_size = 16
        self.learning_rate_decay_start_step = 100_000
        self.learning_rate_decay_min = 5e-10
        self.iterations = 2500
        self.initial_autoregressive_position = 128
        self.use_autoregressive_sampling = False
        self.sampling_temperature = 0
        # serving-side logits filters (beyond-reference: the reference
        # always samples the full distribution); 0 / 1.0 = disabled
        self.sampling_top_k = 0
        self.sampling_top_p = 1.0
        self.sampling_repetition_penalty = 1.0
        self.weight_centralisation = True
        self.shuffle_input_filenames = True
        self.calc_accuracy = False
        self.num_of_sample = 10
        self.web_workers = 1
        self.equal_debugging_items_per_check = 16
        self.group_linear_factor = 2
        self.embedding_stddev = 0.04
        self.color_quantization_value = 256
        self.experts = 64
        # routed (top-k) MoE defaults; per-layer flags top_k<k> /
        # capacity_factor<f> on the routed mixture_of_experts override these
        self.moe_top_k = 1
        self.moe_capacity_factor = 1.25
        # Switch/GShard auxiliary losses on the routed MoE router (0 = off,
        # the reference-parity default — the reference's soft-MoE has no
        # router).  Gradients are injected via a custom_vjp on the router
        # logits so they are exact under every memory strategy; the reported
        # total loss stays the task loss (see model/basic.py).
        self.moe_balance_loss = 0.0
        self.moe_router_z_loss = 0.0
        # every N steps, run a forward-only routing probe and merge per-layer
        # expert utilization / dropped-token stats into the step metrics
        self.moe_metrics_interval = 0
        # base of the rotary position embedding's frequencies (attention
        # flag "rope"): feature pair i turns by pos * rope_theta^(-2i/width)
        self.rope_theta = 10000.0
        # the standard attention (flags "rope" / "nope", model/spatial.py):
        # query heads per key / value head (1 = multi-head attention; more =
        # grouped queries, heads // query_group K/V heads) and the softmax
        # scale (0 = features_per_head ** -0.5)
        self.query_group = 1
        self.attention_scale = 0.0
        # attention flag "yarn" (rotary positions at YaRN's frequencies,
        # model/spatial.py yarn_inv_freq): the context's growth factor, the
        # positions the frequencies were trained on, the turns in them above
        # which a frequency stays / below which it is divided by the factor,
        # and what multiplies cos and sin (0 = 0.1 ln(factor) + 1)
        self.rope_yarn_factor = 1.0
        self.rope_yarn_original_positions = 4096
        self.rope_yarn_beta_fast = 32.0
        self.rope_yarn_beta_slow = 1.0
        self.rope_yarn_attention_factor = 0.0
        # layer "moe" (model/moe.py): the experts' width beside the dense
        # MLP's (0 = intermediate_feed_forward_multiplier x features); which
        # of the "experts" routed experts THIS layer holds — experts_held
        # consecutive ones from experts_first (0 held = all of them); the
        # chosen probabilities renormalised to sum to one, and a scale on them
        self.expert_width = 0
        self.experts_held = 0
        self.experts_first = 0
        self.moe_norm_topk = False
        self.moe_route_scale = 1.0
        # layer "moe" with flag "router_mlp" (ZAYA1's router): the width of
        # the router's own stream, which one layer's router hands the next
        self.moe_router_width = 256
        # layer "moe" with flag "latent" (LatentMoE): the width of the latent
        # the routed experts read and write, between a projection down before
        # dispatch and one up after combine; with flag "shared_expert": the
        # shared expert's own width (0 = the experts'); with flag
        # "sigmoid_bias": the step of the selection bias's update (its rule:
        # optim/__init__.py selection_bias_rule; 0 = the bias stays)
        self.moe_latent_width = 0
        self.shared_expert_width = 0
        self.moe_bias_rate = 1e-3
        # layer "cca" (compressed convolutional attention, model/cca.py):
        # taps of the depthwise and of the grouped causal conv over the
        # packed q-k latent (the published cca_time0 / cca_time1)
        self.cca_time0 = 2
        self.cca_time1 = 2
        # standard deviation of the matrices that WRITE into the residual
        # stream in layers "cca" (the output projection) and "moe" (the
        # experts' down-projection); 0 = normal(0.02) like every other
        # matrix.  Megatron's scaled initialisation is 0.02 / sqrt(2 x layers)
        self.residual_out_stddev = 0.0
        # layer "mamba" (Mamba-2, model/mamba.py): heads x width of the inner
        # stream, the state's size, the causal depthwise conv's width, and
        # the chunk of the state-space-duality scan
        self.mamba_heads = 64
        self.mamba_head_features = 64
        self.mamba_state = 128
        self.mamba_conv_size = 4
        self.mamba_chunk = 256
        # groups of B / C: head j reads group j // (mamba_heads /
        # mamba_groups), and the gated RMSNorm runs over each group's columns
        self.mamba_groups = 1
        # layer "gated_delta" (gated delta-rule linear attention,
        # model/gated_delta.py): heads, the width of a head's key / query and
        # of its value, the causal depthwise conv's taps, the chunk of the
        # WY form, and whether the write strength beta reaches 2 (negative
        # eigenvalues of the transition) or stops at 1
        self.delta_heads = 30
        self.delta_key_features = 96
        self.delta_value_features = 192
        self.delta_conv_size = 4
        self.delta_chunk = 64
        self.delta_allow_neg_eigval = True
        # layer "kda" (Kimi Delta Attention, model/kda.py: the delta rule
        # with a decay a channel of the key): heads, the width of a head's
        # key / query and of its value (also the inner width of the low-rank
        # decay and gate pairs), the conv's taps
        self.kda_heads = 32
        self.kda_key_features = 128
        self.kda_value_features = 128
        self.kda_conv_size = 4
        # layer "lightning" (decayed linear attention with per-head keys,
        # model/lightning.py): the heads of the WHOLE layer (a head's decay
        # follows its index among them), which of them THIS layer holds —
        # lightning_heads_held consecutive ones from lightning_heads_first
        # (0 held = all) —, a head's width, the chunk of the chunked form, and
        # the groups of heads the output norm normalises together (a rank
        # holds whole groups, so it normalises without an exchange)
        self.lightning_heads = 32
        self.lightning_heads_held = 0
        self.lightning_heads_first = 0
        self.lightning_head_features = 128
        self.lightning_chunk = 256
        self.lightning_norm_groups = 2
        # attention flag "sparse" (block-selected attention, model/sparse.py;
        # MiniCPM4's sparse_config): the pooled keys' window and stride, the
        # keys a block, the blocks a query keeps a K/V group, the leading
        # blocks and the trailing keys it always keeps, and the sequence
        # length up to which the layer is dense
        self.sparse_kernel_size = 32
        self.sparse_kernel_stride = 16
        self.sparse_block_size = 64
        self.sparse_topk = 64
        self.sparse_init_blocks = 1
        self.sparse_window = 2048
        self.sparse_dense_length = 8192
        # attention flag "indexed" (token-level sparse attention with a
        # learned indexer, model/indexer.py; Keye-VL-2.0's sa_config): the
        # indexer's heads, the features of an index query and of the one
        # index key, and the single keys a query keeps (up to so many keys
        # the layer is dense)
        self.index_heads = 16
        self.index_features = 64
        self.index_topk = 2048
        # the eps of layer "norm" and of gated_delta's gated norm (a
        # published config's rms_norm_eps / layer_norm_eps)
        self.norm_epsilon = 1e-5
        # Granite's three multipliers: on the token embedding, on every
        # block's output before it joins the residual stream, and the
        # divisor of the logits; 1 = off
        self.embedding_multiplier = 1.0
        self.residual_multiplier = 1.0
        self.logits_scaling = 1.0
        # the head is the (direct) token embedding itself: one parameter
        self.tie_word_embeddings = False
        # a looped (weight-tied-depth) model, model/loop.py: the whole body
        # and the output blocks run loop_steps times over the SAME weights,
        # each pass's output the next one's input and the head's; an exit
        # gate a token spreads the loss over the passes, less
        # loop_exit_entropy times the entropy of that distribution (only
        # read where loop_steps > 1).  1 = the body runs once
        self.loop_steps = 1
        self.loop_exit_entropy = 0.1
        # a multi-token-prediction module, model/mtp.py (DeepSeek-V3,
        # arXiv:2412.19437 section 2.2): mtp_depth more passes of the SAME
        # head, each over the last output joined to the next token's
        # embedding and through the blocks of mtp_block_config (own
        # weights), with its own cross-entropy added to the step's objective
        # at mtp_loss_weight.  0 = no module, and nothing of it is read
        self.mtp_depth = 0
        self.mtp_loss_weight = 0.3
        self.mtp_block_config: typing.Any = []
        # block-diffusion training, model/denoise.py (BD3-LM,
        # arXiv:2503.09573; SDAR, arXiv:2510.06303): the body runs once over
        # [noised sequence | clean sequence], 2 x sequence_length positions,
        # the noise drawn in the step from its key a block of
        # diffusion_block tokens (a rate t ~ U[diffusion_t_min, 1] a block, a
        # mask a token, the mask token diffusion_mask_id, -1 = the last row
        # of the vocabulary); the loss is over the masked positions at 1 / t.
        # 0 = left to right, and nothing of it is read
        self.diffusion_block = 0
        self.diffusion_t_min = 1e-3
        self.diffusion_mask_id = -1
        self.pkm_axes = 2
        self.use_bit_fold_input_pipeline = False
        self.bit_fold_value = 4
        self.debug_train_step = False
        self.model_mode = 'jannet'
        self.optimizer = 'learning_rate'
        self.multi_loss_strategy = "linear"
        self.memory_reduction_strategy = "revnet"
        self.debug_gradients = False
        self.use_initial_position_embedding = False
        self.intermediate_feed_forward_multiplier = None
        self.intermediate_feed_forward_multiplier_multiplier = None
        self.own_color = "\x1b[32;1m"
        self.other_color = "\x1b[0m"
        self.scale_by_depth = True
        self.z_loss = 1e-4
        self.block_config: typing.Any = [
            {'layer': ["norm-group-shift-scale",
                       "feed_forward-in_relu-group-in_glu_add-in_norm"]},
            {'layer': ["norm-group-std-shift-scale",
                       "attention-in_relu-embedded-relative"]}]
        self.input_block_config: typing.Any = []
        self.output_block_config: typing.Any = []
        self.masked_attention_dimensions = [0]
        self.split_grad_accumulation = True
        self.log_dict_keys: typing.List[str] = []

        # ---- TPU-native additions (defaults keep reference configs unchanged)
        self.sequence_parallel = 1           # size of the 'sequence' mesh axis
        self.mesh_shape_override: typing.Optional[typing.Dict[str, int]] = None
        self.layout_override: typing.Dict[str, str] = {}  # dim name -> mesh axis
        self.pipeline_stages = 1          # GPipe stages over the 'pipe' mesh axis
        self.pipeline_microbatches: typing.Optional[int] = None  # default = stages
        # "gpipe" (default): forward pipeline, autodiff backward.  "1f1b":
        # fused forward+backward schedule with the loss head inside the last
        # stage — O(stages) activation stash instead of O(microbatches)
        # (parallel/pipeline_1f1b.py; text models, linear loss only)
        self.pipeline_schedule = "gpipe"
        # virtual chunks per 1f1b stage (Megatron-style interleaving): each
        # device holds V non-adjacent layer chunks, shrinking the pipeline
        # bubble ~1/V for V× more ring hops.  1 = classic non-interleaved.
        self.pipeline_interleave = 1
        # lax.scan over depth: O(1) program size + bounded live activations
        # (falls back to unrolled blocks when the stack isn't homogeneous)
        self.scan_layers = True
        # pallas flash kernel for plain softmax dot-product attention
        # (single-device; map-bias flags and decode use the dense path)
        self.use_flash_attention = True
        # pallas blocked kernel for the pure learned-map mixer
        # (biased_attention_map WITHOUT dot_product — the flagship mixer):
        # (bias . causal mask) @ value computed blockwise in VMEM with
        # causally-dead blocks skipped.  Decode, prefill, non-128-multiple
        # sequences and sequence-/pipe-sharded meshes keep the dense
        # einsum (a loud fallback line names why)
        self.use_map_mixer_kernel = True
        # stash each flash layer's (out, lse) during the forward so the
        # revnet/momentum backward's recompute skips the forward kernel
        # (model/blocks.py stash channels + flash_precomputed).  Opt-in:
        # costs depth x [batch, seq, heads, d] extra residents — a clear
        # win where attention dominates (long context, ~+30% of the 16k
        # step was recompute-forward kernels), a poor trade at flagship
        # shapes (4+ GB at batch 32).  Consumed by the single-device
        # flash path AND the sequence-parallel zigzag ring (whose
        # strategy-backward recompute otherwise re-runs the whole ring,
        # P hops of kernels and ppermutes, per layer).
        # True/False, or "auto" (default): enable attention-output stashing
        # when the sequence is long enough to pay and the stash fits a small
        # HBM fraction (model/blocks.py resolve_stash) — the measured 16k/32k
        # recipes then need no explicit flag.
        # DEPRECATED ALIAS (PR 11): with remat_policy "auto" an explicit
        # true forces the attention kind of the stash on (the other kinds
        # still resolve by their own rules), false is "recompute"; the
        # policy layer below is the real knob
        self.stash_attention_outputs = "auto"
        # ---- measured remat policy (model/remat.py, docs/PERFORMANCE.md
        # 'Round 11').  What the revnet/momentum backward does about
        # re-materializing block interiors:
        #   "recompute"  — the strategy custom_vjp re-runs each block's
        #                  forward inside jax.vjp (O(1) activation memory;
        #                  the historical default behavior),
        #   "stash"      — recompute, but what is dear to replay per byte
        #                  rides the strategy residuals, both kinds: each
        #                  flash/ring attention layer's (out, lse) (no
        #                  forward attention kernels in the replay; the old
        #                  stash_attention_outputs=true) and
        #                  bottleneck_group_linear's in-projection output
        #                  (no second matmul and, where it contracts a
        #                  mesh-sharded axis, no second all-reduce); under
        #                  the "checkpoint" strategy the experts kind:
        #                  layer moe's three grouped-matmul outputs and
        #                  routing triple, saved by the block's
        #                  jax.checkpoint (model/remat.py),
        #   "save"       — NO custom_vjp: the plain recurrence under native
        #                  scan AD, every linearization residual saved
        #                  (zero recompute, O(depth) residual memory),
        #   "save_dots"  — "save" with each block under jax.checkpoint
        #                  (policy dots_saveable): GEMM outputs saved,
        #                  elementwise recomputed — the middle ground for
        #                  compute-bound chips with spare HBM,
        #   "auto"       — each stash kind by its own rule (attention:
        #                  long-context pays and fits; bottleneck: its
        #                  contraction crosses a 'model' axis > 1 and the
        #                  bytes fit what attention leaves of the budget;
        #                  experts: the strategy is "checkpoint", a moe
        #                  layer, the whole depth's outputs fit 15% of HBM),
        #                  else recompute; the save modes
        #                  are measured opt-ins — the round-11 A/B lost on
        #                  the hbm-bound rig and model/remat.py documents
        #                  the analytic comparison (remat_report) for
        #                  chips where it could win.
        # All four execute the SAME primal recurrence (identical losses;
        # gradients agree to reconstruction ulps — tests/remat_policy_test).
        self.remat_policy = "auto"
        # lax.scan unroll factor for the depth scan (XLA overlap vs memory)
        self.scan_unroll = 1
        self.gradient_checkpointing_policy = "nothing_saveable"
        # held-out validation loss (the driver metric is tokens/sec/chip
        # + VAL LOSS @ 32big_mixer — the reference has no eval loop, this is
        # a gap against the project's own success metric).  Every
        # ``eval_interval`` train steps, run ``eval_steps`` forward-only
        # batches (dropout off, no rng, same mesh/strategy) and log
        # val/loss + val/accuracy.  Eval data: ``eval_dataset_configs``
        # (same schema as dataset_configs) when given; otherwise, with
        # ``eval_holdout_files`` = N > 0, the LAST N files (sorted order) of
        # every text dataset glob are held out of training and evaluated on.
        self.eval_interval = 0               # 0 = no eval
        self.eval_steps = 4
        self.eval_dataset_configs: typing.List[dict] = []
        self.eval_holdout_files = 0
        # web_api: up to this many queued completion requests batch into ONE
        # decode call (decode is cache-read-bandwidth-bound — batch 8 is ~4x
        # batch-1 aggregate throughput, BASELINE.md 'Decoding'); 1 = the
        # reference's strictly-serial completions
        self.serve_batch_size = 8
        # weight-only int8 for serving (core/quant.py): batch-1 decode is
        # weight-READ bound, so int8 weights halve the bytes per generated
        # token; dequantize fuses into the dots.  Off by default (greedy
        # tokens can differ from full precision by quantization error)
        self.serve_quantized_weights = False
        # ---- fault tolerance (docs/RELIABILITY.md) ----
        # N > 0: a non-finite (nan/inf) loss skips that step's update (the
        # jitted step selects the old state on-device) and the run aborts
        # with a diagnostic after N CONSECUTIVE non-finite losses.  Costs one
        # device sync per step to read the loss; 0 = off (reference parity)
        self.nonfinite_loss_tolerance = 0
        # retry budget for transient storage errors (GCS 503s, connection
        # resets) at every GCSFS primitive and checkpoint fs call site:
        # exponential backoff from base_delay, jittered (utils/retry.py)
        self.storage_retry_attempts = 5
        self.storage_retry_base_delay = 0.5
        # ---- serving fault tolerance (docs/RELIABILITY.md 'Serving') ----
        # admission control: pending-request budget for the isolated REST
        # path; at/above it the HTTP child answers 429 + Retry-After instead
        # of enqueueing.  0 = unbounded (reference parity)
        self.serve_queue_limit = 64
        # per-request deadline cap AND default (seconds): clients may pass
        # a smaller timeout_s; expired requests are shed and answered 504
        # instead of silently burning the client's whole timeout
        self.serve_request_deadline_s = 120.0
        # HTTP bodies above this are rejected 400 before being read; 0 = off
        self.serve_max_body_bytes = 1 << 20
        # max_tokens above this cap rejects 400 at the HTTP edge, and an
        # omitted/0 max_tokens is capped to it at parse time; 0 = off
        # (over-asks clamp to the sequence, the pre-guard behavior)
        self.serve_max_response_tokens = 0
        # circuit breaker: after N CONSECUTIVE decode failures requests
        # fast-fail 503 + Retry-After for the cooldown, then one probe
        # half-opens.  0 = breaker off
        self.serve_breaker_threshold = 5
        self.serve_breaker_cooldown_s = 30.0
        # supervision: a crashed HTTP subprocess is relaunched with
        # exponential backoff from the base delay, at most this many times
        # (0 = die on first child exit, the pre-guard behavior)
        self.serve_child_max_restarts = 5
        self.serve_child_restart_backoff_s = 0.5
        # /health answers 503 "stale" once the device-loop heartbeat is
        # older than this, so a status-code-only liveness probe restarts a
        # permanently wedged loop.  0 = off (a long decode also ages the
        # heartbeat — pick a threshold above the worst-case decode)
        self.serve_heartbeat_stale_s = 0.0
        # ---- continuous-batching serving engine (docs/SERVING.md) ----
        # which device loop serves completions on the isolated REST path:
        # "batch" = batch-to-completion (drain -> one decode -> answer all,
        # the pre-engine behavior), "continuous" = the slot-pool engine
        # (iteration-level scheduling: admit/evict between donated chunk
        # steps, per-slot end detection; REQUIRES a text model with a
        # streaming decode form — serve() refuses to start otherwise),
        # "auto" = continuous when the deployment can carry it, batch
        # fallback otherwise (stub interfaces, video models)
        self.serve_engine = "auto"
        # engine slot-pool width: requests decoding concurrently in ONE
        # donated chunk step; KV-pool HBM and per-step compute scale
        # linearly with it (the engine analogue of serve_batch_size)
        self.serve_slots = 8
        # per-dispatch iteration budget while any admitted request is still
        # walking its prompt region (prefill interleaved with decode):
        # larger reaches the long prompt's first token in fewer host
        # round-trips, smaller re-checks admit/evict/answer more often —
        # scheduling only happens at chunk boundaries.  Steady-state decode
        # uses decode_chunk_tokens
        self.serve_prefill_chunk_tokens = 128
        # ---- paged KV cache + prefix sharing (docs/SERVING.md) ----
        # replace the engine's fixed per-slot KV stripes with a block pool
        # (infer/paged.py): device KV memory tracks live tokens instead of
        # slots x worst-case length, and prompts sharing a cached prefix
        # (the common-system-prompt chat pattern) reference the same blocks
        # and skip prefill over the shared span (copy-on-write at the
        # divergence point).  "off" = the plain slot engine, byte-identical
        # to the pre-paging behavior; "on" = required (serving refuses to
        # start when the geometry cannot page); "auto" = paged when the
        # deployment can carry it, plain slot engine otherwise.  Greedy
        # output is bit-identical to the plain engine either way
        self.kv_paging = "off"
        # tokens per KV block (the paging granularity): smaller tracks live
        # tokens tighter and shares shorter prefixes; larger means fewer,
        # cheaper table entries.  Must divide the sequence length in patches
        self.kv_block_tokens = 16
        # device block-pool capacity in blocks; 0 = auto
        # (serve_slots x sequence_blocks — capacity parity with the slot
        # engine).  Smaller pools oversubscribe the slots: admissions whose
        # worst-case extent cannot be reserved QUEUE until blocks free up
        # (never an error), and finished prompts stay cached in the radix
        # tree as refcount-0 blocks until LRU eviction reclaims them
        self.kv_pool_blocks = 0
        # ---- multi-replica serving tier (docs/SERVING.md) ----
        # N >= 2 serves THIS config as N engine replica processes behind a
        # device-free router (infer/router.py + distributed/replica_fleet.py)
        # doing prefix-affinity + least-loaded dispatch with a per-replica
        # circuit breaker; the router port is the configured serving port,
        # replicas bind the ports above it.  0/1 = single-replica serving
        # (the pre-tier behavior, byte-identical)
        self.serve_replicas = 0
        # router-side prefix-affinity window: requests whose first N tokens
        # match are routed to the same replica (maximizing its radix-tree
        # hit rate) unless it is overloaded past serve_affinity_slack
        # in-flight requests more than the least-loaded replica
        self.serve_affinity_tokens = 32
        self.serve_affinity_slack = 4
        # ---- disaggregated prefill/decode tier (docs/SERVING.md) ----
        # split the replica tier into CLASSES, e.g. "prefill:1,decode:2":
        # prefill-class replicas compute each distinct prompt prefix once,
        # infer/kv_transfer.py streams the finished KV blocks to decode-
        # class replicas, and the router's global prefix index routes
        # follow-up requests to whoever holds the blocks.  "" = symmetric
        # (classless) tier, byte-identical to today.  Implies the replica
        # count when serve_replicas is unset; requires kv_paging
        self.serve_replica_classes = ""
        # the class THIS process serves under — set per replica by the
        # fleet (distributed/replica_fleet.py), not by hand; surfaces on
        # /health so the router and forensics can tell classes apart
        self.serve_replica_class = ""
        # cap on blocks per /kv/blocks export (0 = uncapped): bounds one
        # migration's payload on replicas with huge cached trees
        self.kv_transfer_max_blocks = 0
        # router-side timeout for one /kv/blocks export or inject leg
        self.kv_transfer_timeout_s = 30.0
        # ---- speculative decoding on the slot engine (docs/SERVING.md) ----
        # draft-and-verify on the continuous engine: each slot runs k cheap
        # draft steps with a quarter-width draft model, then ONE width-(k+1)
        # full-model verify step scores every drafted position; the host
        # accepts the longest matching prefix between donated chunk calls
        # (greedy output stays bit-identical to the plain engine).  "off" =
        # never; "draft" = required (serving refuses to start without a
        # usable draft); "auto" = speculate when a draft is configured and
        # both models support multi-position decode, plain continuous
        # serving otherwise
        self.spec_decode = "off"
        # the draft model: a config JSON (e.g. the committed quarter-width
        # configs/1b_long_context_draft_247m.json) or a checkpoint dir
        # containing config.json; its checkpoints restore from its own
        # model_path alongside the target's (infer/spec.py)
        self.spec_draft_model_path = ""
        # draft tokens per verify (k): each round drafts k tokens and one
        # verify scores k+1 positions, emitting between 1 (total rejection
        # — the verify's own token, so forward progress never stalls) and
        # k+1 (full acceptance + the bonus token) tokens per slot
        self.spec_draft_tokens = 4
        # self-disable floor: when the measured sliding-window acceptance
        # rate drops below this, the engine logs loudly, flips the
        # hbnlp_spec_state gauge, and PERMANENTLY reverts this process to
        # the plain continuous engine — a workload the draft cannot predict
        # must degrade to plain-speed serving, not crawl through rejected
        # drafts.  0 = never self-disable
        self.spec_min_accept_rate = 0.2
        # ---- telemetry (docs/OBSERVABILITY.md) ----
        # master switch for the TRAIN LOOP's per-step registry series: the
        # step spans' histograms (train/step_dispatch, data/next,
        # data/place), the token counter, prefetcher gauges, the JSONL dump.
        # It adds no device sync and changes no timing; the spans' trace
        # annotations are written either way.  Off = exactly ZERO registry
        # calls on the step hot path.  Set-up and rare-event sites (model
        # init, compiles, metric log, storage retries, checkpoint IO,
        # serving decode rounds) record regardless — their cadence is never
        # per-step — and GET /metrics is always served
        self.telemetry_enabled = False
        # with telemetry on: append a registry-snapshot JSONL line to
        # <model_path>/telemetry.jsonl at most every N seconds (checked at
        # the metric-log cadence).  0 = no JSONL dump
        self.telemetry_jsonl_interval_s = 0.0
        # opt-in: SIGUSR2 captures a jax.profiler trace of the next
        # telemetry_profile_steps steps into <model_path>/profile/
        # on_demand_<step> (a second SIGUSR2 stops early).  Independent of
        # telemetry_enabled — profiling has no per-step cost until triggered
        self.telemetry_profile_on_signal = False
        self.telemetry_profile_steps = 10
        # flight recorder (docs/OBSERVABILITY.md 'Flight recorder'):
        # bounded ring of typed events (step records, membership/lease
        # transitions, breaker trips, admission/eviction decisions,
        # checkpoint commits, collective-phase markers) recorded
        # UNCONDITIONALLY at rare-event cadence and dumped as
        # <model_path>/blackbox_p<rank>.jsonl on every exit path — crash
        # unwind, exit-143 emergency save, exit-144 membership force-exit,
        # SIGUSR2 on demand.  This is the ring capacity; 0 disables the
        # blackbox dump (the ring still records in-memory)
        self.telemetry_blackbox_events = 4096
        # size cap for <model_path>/telemetry.jsonl (and any rotating
        # telemetry file): past this many MiB the file rotates to .1/.2/...
        # keeping telemetry_keep_files generations, so a week-long run
        # cannot fill the disk.  0 = unbounded (the historical behavior);
        # remote (gs://) paths stay unbounded — rotation needs rename
        self.telemetry_max_file_mb = 64.0
        self.telemetry_keep_files = 2
        # ---- request tracing (docs/OBSERVABILITY.md 'Request tracing') --
        # mint a trace id at the router (or the HTTP edge when
        # unreplicated), propagate it header -> request tuple -> scheduler
        # -> engine hooks, and close spans for queue-wait, admission,
        # per-chunk prefill/decode occupancy, paged-KV block waits and
        # spec rounds — exported per-request as Chrome-trace JSON under
        # <model_path>/traces/ and cross-process via the blackbox events
        # file (scripts/forensics.py --trace merges them).  Off = zero
        # overhead and byte-identical serving
        self.trace_requests = False
        # overlap the next batch's host->device transfer with the running
        # device step (run/train_loop.py _AsyncFeeder): the loop starts a
        # device_put / multi-host shard placement for batch N+1 right after
        # dispatching step N, so the step-phase spans' data_wait/dispatch
        # no longer serialize host transfer against device compute.  Off =
        # the historical fetch-then-dispatch ordering
        self.async_input_transfer = True
        # ---- multi-host runtime (docs/DISTRIBUTED.md) ----
        # route checkpoint saves (cadence AND emergency) through the
        # double-buffered background saver: the step thread pays only the
        # device->host staging copy; serialization, fs writes, and the
        # pod-wide commit barrier run on a saver thread
        # (distributed/async_checkpoint.py).  Off = the synchronous save
        self.checkpoint_async = False
        # coordination-service barrier timeout (seconds) for the async
        # checkpoint commit protocol: a peer that died mid-save surfaces as
        # a named timeout here instead of hanging the pod forever
        self.distributed_barrier_timeout_s = 600.0
        # ---- elastic pod training (docs/DISTRIBUTED.md 'Elasticity') ----
        # each process maintains a heartbeat lease in the coordination-
        # service KV (distributed/elastic.py): a peer whose lease lapses
        # (SIGKILLed host, wedged rank) is detected in ~elastic_lease_
        # timeout_s and every survivor exits MEMBERSHIP_EXIT_CODE (144) so
        # the elastic controller (scripts/run_manager.py --elastic) can
        # re-form the pod at the surviving world size from the freshest
        # complete checkpoint — no human, no fixed --num-processes.  Off =
        # the rigid fleet (a dead rank hangs peers until jax's own
        # heartbeat timeout, and relaunch needs the full original world
        # size)
        self.elastic_training = False
        # seconds between lease heartbeats (KV writes on the coordinator's
        # gRPC channel — no device collectives, safe during jitted steps)
        self.elastic_lease_interval_s = 1.0
        # a peer lease older than this = membership change.  Must
        # comfortably exceed the interval; GC pauses and storage stalls
        # shorter than this never false-positive
        self.elastic_lease_timeout_s = 10.0
        # after detecting a lapse the agent gives the main thread this long
        # to exit through the loop's own membership check (between steps)
        # before force-exiting the process — the main thread may be wedged
        # in a collective against the dead rank and can never finish
        self.elastic_exit_grace_s = 3.0
        # straggler detector (docs/OBSERVABILITY.md 'Flight recorder'):
        # the chief's lease agent reads every rank's step progress off the
        # lease heartbeats and flags a slow-but-alive rank — one whose
        # published step lags the fleet and whose time-since-last-advance
        # exceeds this factor x the fleet-median step interval — BEFORE its
        # lease lapses (a wedged main thread keeps heartbeating forever;
        # this is the only signal that catches it).  0 = off
        self.elastic_straggler_factor = 4.0

        self.unknown_config_keys: typing.List[str] = []
        for k, v in config.items():
            if k not in self.__dict__:
                print(f"WARNING: Unknown ModelParameter {k}={v!r}")
                self.unknown_config_keys.append(k)
            self.__dict__[k] = v

        # ---- validation / derivation (reference :189-271)
        assert self.macro_batching > 0, "macro_batching must be >= 1"
        if self.nonfinite_loss_tolerance < 0:
            raise ValueError("nonfinite_loss_tolerance must be >= 0 "
                             f"(0 = off), got {self.nonfinite_loss_tolerance}")
        if self.storage_retry_attempts < 1:
            raise ValueError("storage_retry_attempts must be >= 1, got "
                             f"{self.storage_retry_attempts}")
        if self.storage_retry_base_delay < 0:
            # time.sleep raises on negatives — the typo would replace every
            # retry with a ValueError masking the real storage error
            raise ValueError("storage_retry_base_delay must be >= 0, got "
                             f"{self.storage_retry_base_delay}")
        # serving-guard knobs: 0 disables the mechanism; a negative value is
        # always a typo and would surface as bizarre behavior deep in the
        # serve loop (e.g. time.sleep raising)
        for knob in ("serve_queue_limit", "serve_max_body_bytes",
                     "serve_max_response_tokens", "serve_breaker_threshold",
                     "serve_breaker_cooldown_s", "serve_child_max_restarts",
                     "serve_child_restart_backoff_s",
                     "serve_heartbeat_stale_s"):
            v = getattr(self, knob)
            if v < 0:
                raise ValueError(f"{knob} must be >= 0, got {v}")
        for knob in ("telemetry_jsonl_interval_s",
                     "telemetry_blackbox_events", "telemetry_max_file_mb",
                     "elastic_straggler_factor"):
            if getattr(self, knob) < 0:
                raise ValueError(f"{knob} must be >= 0 (0 = off), got "
                                 f"{getattr(self, knob)}")
        if self.telemetry_keep_files < 1:
            raise ValueError("telemetry_keep_files must be >= 1, got "
                             f"{self.telemetry_keep_files}")
        if self.telemetry_profile_steps < 1:
            raise ValueError("telemetry_profile_steps must be >= 1, got "
                             f"{self.telemetry_profile_steps}")
        if self.distributed_barrier_timeout_s <= 0:
            raise ValueError("distributed_barrier_timeout_s must be > 0 "
                             "(it bounds the async-save commit rendezvous), "
                             f"got {self.distributed_barrier_timeout_s}")
        if self.elastic_lease_interval_s <= 0:
            raise ValueError("elastic_lease_interval_s must be > 0, got "
                             f"{self.elastic_lease_interval_s}")
        if self.elastic_lease_timeout_s <= self.elastic_lease_interval_s:
            # a timeout at/below the heartbeat cadence would declare every
            # peer dead between two of its own beats
            raise ValueError("elastic_lease_timeout_s must exceed "
                             "elastic_lease_interval_s, got "
                             f"{self.elastic_lease_timeout_s} <= "
                             f"{self.elastic_lease_interval_s}")
        if self.elastic_exit_grace_s < 0:
            raise ValueError("elastic_exit_grace_s must be >= 0, got "
                             f"{self.elastic_exit_grace_s}")
        if self.serve_request_deadline_s <= 0:
            raise ValueError("serve_request_deadline_s must be > 0 (it is "
                             "the default deadline, not just a cap), got "
                             f"{self.serve_request_deadline_s}")
        # tri-state like decode_loop: a typo would silently serve through
        # the wrong engine
        if self.serve_engine not in ("auto", "batch", "continuous"):
            raise ValueError("serve_engine must be \"auto\", \"batch\" or "
                             f"\"continuous\", got {self.serve_engine!r}")
        if self.serve_slots < 1:
            raise ValueError("serve_slots must be >= 1, got "
                             f"{self.serve_slots}")
        if self.serve_prefill_chunk_tokens < 1:
            raise ValueError("serve_prefill_chunk_tokens must be >= 1, got "
                             f"{self.serve_prefill_chunk_tokens}")
        # tri-state like serve_engine: a typo would silently serve through
        # the wrong KV layout
        if self.kv_paging not in ("off", "on", "auto"):
            raise ValueError("kv_paging must be \"off\", \"on\" or "
                             f"\"auto\", got {self.kv_paging!r}")
        if self.kv_block_tokens < 1:
            raise ValueError("kv_block_tokens must be >= 1, got "
                             f"{self.kv_block_tokens}")
        if self.kv_pool_blocks < 0:
            raise ValueError("kv_pool_blocks must be >= 0 (0 = auto), got "
                             f"{self.kv_pool_blocks}")
        for knob in ("serve_replicas", "serve_affinity_tokens",
                     "serve_affinity_slack", "kv_transfer_max_blocks"):
            if getattr(self, knob) < 0:
                raise ValueError(f"{knob} must be >= 0, got "
                                 f"{getattr(self, knob)}")
        if self.kv_transfer_timeout_s <= 0:
            raise ValueError("kv_transfer_timeout_s must be > 0, got "
                             f"{self.kv_transfer_timeout_s}")
        if self.serve_replica_class not in ("", "prefill", "decode"):
            raise ValueError("serve_replica_class must be \"\", \"prefill\""
                             f" or \"decode\", got "
                             f"{self.serve_replica_class!r}")
        if self.serve_replica_classes:
            # parse eagerly: a topology typo must fail at config load, not
            # after N model loads; the router re-derives the same list
            from .infer.router import parse_replica_classes
            classes = parse_replica_classes(self.serve_replica_classes)
            if self.serve_replicas and self.serve_replicas != len(classes):
                raise ValueError(
                    f"serve_replicas={self.serve_replicas} contradicts "
                    f"serve_replica_classes "
                    f"({self.serve_replica_classes!r} = "
                    f"{len(classes)} replicas)")
            if self.kv_paging == "off":
                raise ValueError(
                    "serve_replica_classes needs kv_paging (block "
                    "streaming moves paged-pool blocks); set kv_paging to "
                    "\"on\" or \"auto\"")
        # tri-state like serve_engine: a typo would silently serve without
        # (or refuse to serve with) speculation
        if self.spec_decode not in ("off", "draft", "auto"):
            raise ValueError("spec_decode must be \"off\", \"draft\" or "
                             f"\"auto\", got {self.spec_decode!r}")
        if self.spec_draft_tokens < 1:
            raise ValueError("spec_draft_tokens must be >= 1, got "
                             f"{self.spec_draft_tokens}")
        if not 0 <= self.spec_min_accept_rate <= 1:
            raise ValueError("spec_min_accept_rate must be in [0, 1] "
                             "(0 = never self-disable), got "
                             f"{self.spec_min_accept_rate}")
        # the serving-default repetition penalty reaches _repetition_penalty
        # whenever a request omits a value (sample mode, REPL, batched
        # rows); r <= 0 would inf/NaN seen tokens' logits — apply the same
        # >0 check the REST boundary applies to explicit request values.
        # top_k/top_p need no check: the sampler defines behavior for every
        # value (top_k <= 0 disables; top_p = 0 keeps the argmax, >= 1
        # disables — infer/sampler.py _filter_logits)
        if self.sampling_repetition_penalty <= 0:
            raise ValueError("sampling_repetition_penalty must be > 0, got "
                             f"{self.sampling_repetition_penalty}")
        # tri-state like stash_attention_outputs: any other string would
        # silently route serving through an unintended decode loop
        if self.decode_loop not in ("auto", "fused", "stepped"):
            raise ValueError("decode_loop must be \"auto\", \"fused\" or "
                             f"\"stepped\", got {self.decode_loop!r}")
        if self.decode_chunk_tokens < 1:
            raise ValueError("decode_chunk_tokens must be >= 1, got "
                             f"{self.decode_chunk_tokens}")
        if self.decode_stepped_min_cache_gb < 0:
            raise ValueError("decode_stepped_min_cache_gb must be >= 0, got "
                             f"{self.decode_stepped_min_cache_gb}")
        # tri-state: any other string would fall through bool("...") == True
        # and silently force-enable stashing ("false" enabling a feature)
        if self.stash_attention_outputs not in (True, False, "auto"):
            raise ValueError("stash_attention_outputs must be true, false, "
                             f"or \"auto\", got "
                             f"{self.stash_attention_outputs!r}")
        if self.remat_policy not in ("auto", "recompute", "stash", "save",
                                     "save_dots"):
            raise ValueError("remat_policy must be \"auto\", \"recompute\", "
                             "\"stash\", \"save\" or \"save_dots\", got "
                             f"{self.remat_policy!r}")
        # the checkpoint-strategy jax.checkpoint sites consume this name
        # via getattr (model/blocks.py _checkpoint_policy); validate here so
        # a typo is a clear config error, not an AttributeError mid-trace
        import jax
        if not hasattr(jax.checkpoint_policies,
                       self.gradient_checkpointing_policy):
            raise ValueError(
                "gradient_checkpointing_policy must name a "
                "jax.checkpoint_policies member (e.g. \"nothing_saveable\", "
                f"\"dots_saveable\"), got "
                f"{self.gradient_checkpointing_policy!r}")
        if isinstance(self.position_embedding, str):
            self.position_embedding = self.position_embedding.split('-')
        if isinstance(self.token_embedding, str):
            self.token_embedding = self.token_embedding.split('-')
        if isinstance(self.output_embedding, str):
            self.output_embedding = self.output_embedding.split('-')
        if isinstance(self.empty_frame_embedding, str):
            self.empty_frame_embedding = self.empty_frame_embedding.split('-')

        for attr in ("slice_dtype", "storage_dtype", "calculation_dtype",
                     "optimizer_slice_dtype", "optimizer_calculation_dtype",
                     "decode_cache_dtype"):
            v = getattr(self, attr)
            if isinstance(v, str):
                table = _CACHE_DTYPES if attr == "decode_cache_dtype" \
                    else _DTYPES
                setattr(self, attr, table[v])

        self.learning_rate_config = {
            key: cfg if isinstance(cfg, LearningRateConfig) else LearningRateConfig(**cfg)
            for key, cfg in self.learning_rate_config.items()}

        # text-only GPT mode forces the video path off (the reference does
        # this at session bring-up, src/main.py:88-93; doing it here makes
        # the shipped gpt configs load standalone)
        if self.model_mode == 'gpt':
            self.use_language = True
            self.use_video = False
        elif self.model_mode != 'jannet':
            raise ValueError(f"model_mode must be 'jannet' or 'gpt', "
                             f"got {self.model_mode!r}")

        self.multi_loss_strategy = self.multi_loss_strategy.lower()
        if self.multi_loss_strategy not in ("linear", "pcgrad", "mgda"):
            print(f"{self.multi_loss_strategy} unsupported; defaulting to linear")
            self.multi_loss_strategy = "linear"
        if ((self.moe_balance_loss or self.moe_router_z_loss)
                and self.multi_loss_strategy != "linear"):
            # the router aux gradients are injected once per backward pass;
            # pcgrad/mgda run one backward PER loss and would count them twice
            raise ValueError("moe_balance_loss/moe_router_z_loss require "
                             "multi_loss_strategy='linear'")
        if not self.use_language and not self.use_video:
            raise ValueError("Language and video mode are disabled. No model can be built.")
        if self.weight_standardisation and not self.weight_centralisation:
            print("Can't standardise weights without centralizing them first. Enabling it.")
            self.weight_centralisation = True
        if self.features is None and self.features_per_head is None:
            raise ValueError("Either features or features_per_head has to be specified")
        if self.features is None:
            self.features = self.features_per_head * self.heads
        if self.features_per_head is None:
            self.features_per_head = self.features // self.heads
        if self.use_video and (self.frame_width * self.frame_height // self.patch_size) % self.experts:
            raise ValueError("Frame size has to be divisible by number of experts")
        if self.use_video and self.use_language and self.three_axes:
            # the reference's text+frame concat joins txt [b, seq, height(ltp),
            # h, k] with a rank-6 three-axes frame tensor — rank-mismatched in
            # mtf too (/root/reference/src/dataclass.py:334,
            # src/model/__init__.py:88); only the folded single-spatial-axis
            # layout has well-defined concat/slice semantics
            raise ValueError("use_video + use_language requires "
                             "three_axes=false (height and width fold into "
                             "one spatial axis that text tokens join on)")
        if self.query_group < 1 or self.heads % self.query_group:
            raise ValueError(f"query_group {self.query_group} must divide "
                             f"heads {self.heads}")
        for key in ("expert_width", "experts_held", "experts_first",
                    "moe_latent_width", "shared_expert_width"):
            if not isinstance(getattr(self, key), int) \
                    or getattr(self, key) < 0:
                raise ValueError(f"{key} {getattr(self, key)!r} must be a "
                                 "whole number >= 0")
        for key in ("moe_router_width", "cca_time0", "cca_time1"):
            if not isinstance(getattr(self, key), int) \
                    or getattr(self, key) < 1:
                raise ValueError(f"{key} {getattr(self, key)!r} must be a "
                                 "positive whole number")
        if not self.residual_out_stddev >= 0:
            raise ValueError(f"residual_out_stddev "
                             f"{self.residual_out_stddev!r} must be >= 0 "
                             "(0 = 0.02)")
        if not self.moe_bias_rate >= 0:
            raise ValueError(f"moe_bias_rate {self.moe_bias_rate!r} must be "
                             ">= 0")
        if not isinstance(self.mamba_groups, int) or self.mamba_groups < 1 \
                or self.mamba_heads % self.mamba_groups:
            raise ValueError(f"mamba_groups {self.mamba_groups!r} must be a "
                             f"positive divisor of mamba_heads "
                             f"{self.mamba_heads}")
        if self.experts_first + self.experts_held > self.experts:
            raise ValueError(
                f"experts_first {self.experts_first} + experts_held "
                f"{self.experts_held} exceeds experts {self.experts}")
        if self.experts_first and not self.experts_held:
            raise ValueError("experts_first without experts_held")
        if not self.moe_route_scale > 0:
            raise ValueError(f"moe_route_scale {self.moe_route_scale!r} "
                             "must be > 0")
        if self.rope_yarn_factor < 1 or self.rope_yarn_original_positions < 1 \
                or not (self.rope_yarn_beta_fast > self.rope_yarn_beta_slow
                        > 0) or self.rope_yarn_attention_factor < 0:
            raise ValueError(
                "rope_yarn_*: factor >= 1, original_positions >= 1, "
                "beta_fast > beta_slow > 0, attention_factor >= 0 (0 = "
                "0.1 ln(factor) + 1)")
        for key in ("delta_heads", "delta_key_features",
                    "delta_value_features", "delta_chunk", "kda_heads",
                    "kda_key_features", "kda_value_features"):
            if not isinstance(getattr(self, key), int) \
                    or getattr(self, key) < 1:
                raise ValueError(f"{key} {getattr(self, key)!r} must be a "
                                 "positive whole number")
        for key in ("lightning_heads", "lightning_head_features",
                    "lightning_chunk", "lightning_norm_groups",
                    "sparse_kernel_size",
                    "sparse_kernel_stride", "sparse_block_size",
                    "sparse_topk", "index_heads", "index_features",
                    "index_topk"):
            if not isinstance(getattr(self, key), int) \
                    or getattr(self, key) < 1:
                raise ValueError(f"{key} {getattr(self, key)!r} must be a "
                                 "positive whole number")
        for key in ("lightning_heads_held", "lightning_heads_first",
                    "sparse_init_blocks", "sparse_window",
                    "sparse_dense_length"):
            if not isinstance(getattr(self, key), int) \
                    or getattr(self, key) < 0:
                raise ValueError(f"{key} {getattr(self, key)!r} must be a "
                                 "whole number >= 0")
        if self.lightning_heads_first + self.lightning_heads_held \
                > self.lightning_heads:
            raise ValueError(
                f"lightning_heads_first {self.lightning_heads_first} + "
                f"lightning_heads_held {self.lightning_heads_held} exceeds "
                f"lightning_heads {self.lightning_heads}")
        if self.lightning_heads_first and not self.lightning_heads_held:
            raise ValueError("lightning_heads_first without "
                             "lightning_heads_held")
        if self.lightning_heads % self.lightning_norm_groups or any(
                count % (self.lightning_heads // self.lightning_norm_groups)
                for count in (self.lightning_heads_held,
                              self.lightning_heads_first)):
            raise ValueError(
                f"lightning_norm_groups {self.lightning_norm_groups} divides "
                f"lightning_heads {self.lightning_heads}, and a rank holds "
                "whole groups (lightning_heads_held, lightning_heads_first)")
        if self.sparse_kernel_size % self.sparse_kernel_stride \
                or self.sparse_block_size % self.sparse_kernel_stride \
                or self.sparse_window % self.sparse_block_size:
            raise ValueError(
                "sparse_*: sparse_kernel_stride divides sparse_kernel_size "
                "and sparse_block_size, sparse_block_size divides "
                "sparse_window")
        if self.index_features % 2:
            raise ValueError(f"index_features {self.index_features}: rotary "
                             "positions turn pairs of features")
        for key in ("delta_conv_size", "kda_conv_size"):
            if not isinstance(getattr(self, key), int) \
                    or not 1 <= getattr(self, key) <= 128:
                raise ValueError(f"{key} {getattr(self, key)!r} must be 1 to "
                                 "128 taps")
        if not self.norm_epsilon > 0:
            raise ValueError(f"norm_epsilon {self.norm_epsilon!r} must be "
                             "positive")
        if not isinstance(self.loop_steps, int) \
                or isinstance(self.loop_steps, bool) or self.loop_steps < 1:
            raise ValueError(f"loop_steps {self.loop_steps!r} must be a "
                             "whole number >= 1")
        if self.loop_steps > 1:
            entropy = self.loop_exit_entropy
            if isinstance(entropy, bool) \
                    or not isinstance(entropy, (int, float)) or entropy < 0:
                raise ValueError(f"loop_exit_entropy {entropy!r} must be a "
                                 "number >= 0")
            refused = [why for why, hit in (
                (f"memory_reduction_strategy "
                 f"{self.memory_reduction_strategy!r} (the revnet and "
                 "momentum streams are not re-entered: \"checkpoint\" or "
                 "\"none\")",
                 self.memory_reduction_strategy not in ("none",
                                                        "checkpoint")),
                ("use_video", self.use_video),
                ("a contrastive loss", self.contrastive_across_samples
                 or self.contrastive_across_token_embeddings),
                (f"multi_loss_strategy {self.multi_loss_strategy!r}",
                 self.multi_loss_strategy != "linear"),
                ("calc_accuracy", self.calc_accuracy)) if hit]
            if refused:
                raise ValueError(f"loop_steps {self.loop_steps} (a looped "
                                 "model, model/loop.py) refuses "
                                 + "; ".join(refused))
        if not isinstance(self.mtp_depth, int) \
                or isinstance(self.mtp_depth, bool) or self.mtp_depth < 0:
            raise ValueError(f"mtp_depth {self.mtp_depth!r} must be a whole "
                             "number >= 0")
        if self.mtp_depth:
            weight = self.mtp_loss_weight
            if isinstance(weight, bool) \
                    or not isinstance(weight, (int, float)) or weight < 0:
                raise ValueError(f"mtp_loss_weight {weight!r} must be a "
                                 "number >= 0")
            refused = [why for why, hit in (
                ("no mtp_block_config (the module's blocks)",
                 not self.mtp_block_config),
                (f"loop_steps {self.loop_steps} (a looped model)",
                 self.loop_steps > 1),
                (f"memory_reduction_strategy "
                 f"{self.memory_reduction_strategy!r} (the module's blocks "
                 "join no revnet or momentum stream: \"checkpoint\" or "
                 "\"none\")",
                 self.memory_reduction_strategy not in ("none",
                                                        "checkpoint")),
                ("scan_layers", self.scan_layers),
                ("use_video", self.use_video),
                ("a contrastive loss", self.contrastive_across_samples
                 or self.contrastive_across_token_embeddings),
                (f"multi_loss_strategy {self.multi_loss_strategy!r}",
                 self.multi_loss_strategy != "linear"),
                ("a factorized or patched token embedding",
                 bool(self.vocab_weight_factorization)
                 or self.token_patch_size != 1),
                (f"sequence_length {self.sequence_length} under "
                 f"{self.mtp_depth + 1}",
                 self.sequence_length <= self.mtp_depth)) if hit]
            if refused:
                raise ValueError(f"mtp_depth {self.mtp_depth} (a "
                                 "multi-token-prediction module, "
                                 "model/mtp.py) refuses "
                                 + "; ".join(refused))
        block = self.diffusion_block
        if not isinstance(block, int) or isinstance(block, bool) or block < 0:
            raise ValueError(f"diffusion_block {block!r} must be a whole "
                             "number >= 0")
        if block:
            t_min, mask_id = self.diffusion_t_min, self.diffusion_mask_id
            if isinstance(t_min, bool) or not isinstance(t_min, (int, float)) \
                    or not 0 < t_min <= 1:
                raise ValueError(f"diffusion_t_min {t_min!r} must be a "
                                 "number in (0, 1]")
            if not isinstance(mask_id, int) or isinstance(mask_id, bool) \
                    or not -1 <= mask_id < self.vocab_size:
                raise ValueError(f"diffusion_mask_id {mask_id!r} must be a "
                                 "row of the vocabulary, or -1 (the last)")
            refused = [why for why, hit in (
                (f"sequence_length {self.sequence_length} of no whole "
                 f"blocks of {block}", self.sequence_length % block != 0),
                (f"loop_steps {self.loop_steps} (a looped model)",
                 self.loop_steps > 1),
                (f"mtp_depth {self.mtp_depth} (a multi-token-prediction "
                 "module)", bool(self.mtp_depth)),
                (f"memory_reduction_strategy "
                 f"{self.memory_reduction_strategy!r} (revnet and momentum "
                 "streams: \"checkpoint\" or \"none\")",
                 self.memory_reduction_strategy not in ("none",
                                                        "checkpoint")),
                ("scan_layers", self.scan_layers),
                ("use_video", self.use_video),
                ("a contrastive loss", self.contrastive_across_samples
                 or self.contrastive_across_token_embeddings),
                (f"multi_loss_strategy {self.multi_loss_strategy!r}",
                 self.multi_loss_strategy != "linear"),
                ("calc_accuracy", self.calc_accuracy),
                ("input_dropout", self.input_dropout > 0),
                ("use_initial_position_embedding",
                 self.use_initial_position_embedding),
                ("a factorized or patched token embedding",
                 bool(self.vocab_weight_factorization)
                 or self.token_patch_size != 1),
                ("a leading block (input_block_config)",
                 bool(self.input_block_config))) if hit]
            if refused:
                raise ValueError(f"diffusion_block {block} (block-diffusion "
                                 "training, model/denoise.py) refuses "
                                 + "; ".join(refused))
        if self.tie_word_embeddings and (self.vocab_weight_factorization
                                         or self.token_patch_size != 1
                                         or self.use_video):
            raise ValueError("tie_word_embeddings needs a direct token "
                             "embedding: vocab_weight_factorization 0, "
                             "token_patch_size 1, text only")
        if self.residual_multiplier != 1 and \
                self.memory_reduction_strategy not in ("none", "checkpoint"):
            raise ValueError("residual_multiplier scales a block's output "
                             "where it joins the plain residual stream: "
                             "memory_reduction_strategy \"none\" or "
                             "\"checkpoint\"")
        if self.intermediate_feed_forward_multiplier_multiplier is not None:
            self.intermediate_feed_forward_multiplier = (
                self.group_linear_factor
                * self.intermediate_feed_forward_multiplier_multiplier / self.heads)
        if self.intermediate_feed_forward_multiplier is None:
            self.intermediate_feed_forward_multiplier = self.group_linear_factor / self.heads
        if not self.use_video and self.language_token_per_frame != self.sequence_length:
            self.language_token_per_frame = self.sequence_length
        if self.use_random_dataloader:
            # deliberately unseeded: this IS the entropy source for the
            # auto-generated data_seed  # graft-lint: allow[unseeded-rng]
            self.data_seed = int(np.random.default_rng().integers(0, 1_000_000))
            # the chosen seed is printed here AND lands in the run_config_*
            # json + a metrics.jsonl note (run/train_loop.py) so the run is
            # reproducible after the fact: rerun with this data_seed and
            # use_random_dataloader=false
            print(f'WARNING: use_random_dataloader: data_seed '
                  f'auto-generated -> {self.data_seed} (set data_seed='
                  f'{self.data_seed} to reproduce this data order)')
        if self.combine_assignments:
            # the reference flag merged mtf assign ops into one op ("needs
            # more memory but it's faster", dataclass.py:77); the jitted
            # train step already applies every variable update in one fused
            # XLA program, so the combined behaviour is always on here
            print("combine_assignments: inherent in the jitted step "
                  "(all updates run in one fused program); no separate effect")

        # ---- mesh derivation: reference's 2-D batch x heads mesh (:247-252),
        # extended with optional sequence (long-context) and pipe (pipeline
        # stages — new capability, reference has none) axes.
        if self.mesh_shape_override:
            self.mesh_shape = dict(self.mesh_shape_override)
        else:
            denom = self.heads * self.sequence_parallel * self.pipeline_stages
            data_par = max(1, self.tpu_size // denom)
            self.mesh_shape = {}
            if data_par > 1:
                self.mesh_shape["data"] = data_par
            if self.heads > 1:
                self.mesh_shape["model"] = self.heads
            if self.sequence_parallel > 1:
                self.mesh_shape["sequence"] = self.sequence_parallel
            if self.pipeline_stages > 1:
                self.mesh_shape["pipe"] = self.pipeline_stages
            if not self.mesh_shape:
                self.mesh_shape = {"data": 1}
        # pipeline_stages always mirrors the mesh's pipe axis (1 when absent);
        # an explicit request that the override mesh cannot honour is an error,
        # not a silent fallback
        if (self.mesh_shape_override and "pipe" not in self.mesh_shape
                and self._raw_config.get("pipeline_stages", 1) > 1):
            raise ValueError(
                "pipeline_stages > 1 requires a 'pipe' axis in mesh_shape_override")
        self.pipeline_stages = self.mesh_shape.get("pipe", 1)
        if self.pipeline_stages > 1 and self.loop_steps > 1:
            raise ValueError(
                f"loop_steps {self.loop_steps} (a looped model, "
                "model/loop.py) refuses a pipeline mesh: pipeline_stages "
                f"{self.pipeline_stages} (a stage's blocks are not "
                "re-entered)")
        if self.pipeline_stages > 1 and self.mtp_depth:
            raise ValueError(
                f"mtp_depth {self.mtp_depth} (a multi-token-prediction "
                "module, model/mtp.py) refuses a pipeline mesh: "
                f"pipeline_stages {self.pipeline_stages} (the module reads "
                "the last stage's output and the first stage's table)")
        if self.diffusion_block and (self.pipeline_stages > 1
                                     or self.mesh_shape.get("sequence", 1) > 1):
            raise ValueError(
                f"diffusion_block {self.diffusion_block} (block-diffusion "
                "training, model/denoise.py) refuses a pipeline mesh and a "
                f"sequence-sharded one: mesh {self.mesh_shape} (the noised "
                "half reads the clean half's keys)")
        if self.pipeline_stages > 1 and self.depth % self.pipeline_stages:
            raise ValueError(
                f"depth={self.depth} must divide into pipe={self.pipeline_stages} stages")
        if self.pipeline_microbatches is None:
            self.pipeline_microbatches = self.pipeline_stages
        self.pipeline_interleave = max(1, int(self.pipeline_interleave or 1))
        if self.pipeline_interleave > 1:
            if self.pipeline_schedule != "1f1b":
                raise ValueError("pipeline_interleave > 1 requires "
                                 "pipeline_schedule='1f1b'")
            chunks = self.pipeline_stages * self.pipeline_interleave
            if self.pipeline_stages > 1 and self.depth % chunks:
                raise ValueError(
                    f"depth={self.depth} must divide into "
                    f"{chunks} virtual chunks "
                    f"(pipe={self.pipeline_stages} x "
                    f"interleave={self.pipeline_interleave})")
            if self.pipeline_microbatches % self.pipeline_stages:
                raise ValueError("interleaved 1f1b needs "
                                 "pipeline_microbatches divisible by "
                                 "pipeline_stages")
        # dim-name -> mesh-axis layout rules ("batch:b,heads:h" analogue);
        # layout_override adds/replaces rules (e.g. {"experts": "model"} for
        # expert-parallel soft-MoE with replicated heads)
        self.layout = {}
        if "data" in self.mesh_shape:
            self.layout["batch"] = "data"
        if "model" in self.mesh_shape:
            self.layout["heads"] = "model"
        if "sequence" in self.mesh_shape:
            self.layout["sequence"] = "sequence"
        # a None value in layout_override deletes the rule (un-maps the dim)
        self.layout.update(self.layout_override)
        self.layout = {k: v for k, v in self.layout.items() if v is not None}

        self.block_config = [BlockConfig(c, self.memory_reduction_strategy)
                             for c in self.block_config]
        self.input_block_config = [BlockConfig(c, "checkpoint") for c in self.input_block_config]
        self.output_block_config = [BlockConfig(c, "checkpoint") for c in self.output_block_config]
        self.mtp_block_config = [BlockConfig(c, self.memory_reduction_strategy)
                                 for c in self.mtp_block_config]

        self.time_patch_size = self.sequence_length // self.time_patch
        # positions a sequence of the body's stream (block-diffusion training
        # runs the noised sequence beside the clean one)
        self.stream_length = self.sequence_length \
            * (2 if self.diffusion_block else 1)
        self.frame_height_patch = self.frame_height // self.patch_size
        self.frame_width_patch = self.frame_width // self.patch_size
        self.channel_color_size = self.color_channels * self.time_patch * self.patch_size ** 2
        self.fold_count = 32 // self.bit_fold_value
        if 2 ** self.bit_fold_value < self.color_quantization_value and self.use_bit_fold_input_pipeline:
            raise ValueError("fold value must be >= color bit value when folding input")
        self.language_token_patch = self.language_token_per_frame // self.token_patch_size
        if self.use_bit_fold_input_pipeline:
            self.channel_color_size //= self.fold_count

        # ---- named dims (reference :273-316)
        self.product_key_value_vectors = self.features_per_head ** 2
        self.product_key_value_dim = Dim("product_key_value_dim", self.product_key_value_vectors)
        self.head_dim = Dim("heads", self.heads)
        self.head_dimensions = [self.head_dim]
        self.key_dim = Dim("features_per_head", self.features // self.heads)
        self.sequence_per_head_dim = Dim("sequence_per_head", self.time_patch_size // self.heads)
        self.pkm_dim = Dim("pkm_axes", self.pkm_axes)
        self.feature_dims = [self.head_dim, self.key_dim]
        self.intermediate = [Dim("intermediate",
                                 int(self.heads * self.key_dim.size
                                     * self.intermediate_feed_forward_multiplier))]
        # the width of ONE routed (or shared) expert of layer moe
        self.expert_intermediate = [Dim("intermediate", self.expert_width)] \
            if self.expert_width else self.intermediate
        self.expert_dim = Dim("experts", self.experts)
        self.macro_batch_dim = Dim("batch", self.train_batch_size * self.macro_batching)
        self.vocab_dim = Dim("vocab", self.vocab_size)
        self.batch_dim = Dim("batch", self.train_batch_size)
        self.frame_input_sequence = Dim("_sequence", self.time_patch_size + 1)

        frame_input_shape = [self.batch_dim, self.frame_input_sequence]
        if self.three_axes:
            frame_input_shape += [Dim("height", self.frame_height_patch),
                                  Dim("width", self.frame_width_patch)]
        else:
            frame_input_shape += [Dim("height", self.frame_height_patch * self.frame_width_patch)]
        self.color_channel_dim = Dim("color_channels", self.channel_color_size)
        frame_input_shape += [self.color_channel_dim]
        self.frame_input_shape = frame_input_shape

        # the body's stream: the trained tokens, and under block-diffusion
        # training (diffusion_block > 0) the noised sequence before them;
        # the batch's tokens are token_sequence_dim long either way
        self.token_sequence_dim = Dim("sequence", self.time_patch_size)
        self.sequence_dim = Dim("sequence",
                                self.stream_length // self.time_patch)
        self.token_patch_dim = Dim("language_token_patch", self.token_patch_size)
        self.token_dim_shape = [self.batch_dim, self.token_sequence_dim,
                                self.token_patch_dim]
        self.frame_mask_shape = [self.batch_dim, self.token_sequence_dim]

        self.input_pipeline_shape: typing.Dict[str, list] = {}
        if self.use_video:
            self.input_pipeline_shape['frame'] = self.frame_input_shape
            self.input_pipeline_shape['cat_mask_x'] = self.frame_mask_shape
            self.input_pipeline_shape['cat_mask_y'] = self.frame_mask_shape
            self.input_pipeline_shape['vid_msk_src'] = self.frame_mask_shape
            self.input_pipeline_shape['vid_msk_tgt'] = self.frame_mask_shape
            self.discrete_dim = [Dim("discrete", self.channel_color_size * self.color_quantization_value)]
            self.discrete_color_dim = Dim("color_quantization", self.color_quantization_value)
        if self.use_language:
            self.input_pipeline_shape['token_x'] = self.token_dim_shape
            self.input_pipeline_shape['token_y'] = self.token_dim_shape
        if self.use_language and self.use_video:
            self.token_dim_shape = [self.batch_dim, self.sequence_dim,
                                    Dim("height", self.language_token_patch),
                                    self.token_patch_dim]
            self.input_pipeline_shape['token_x'] = self.token_dim_shape
            self.input_pipeline_shape['token_y'] = self.token_dim_shape
            self.input_pipeline_shape['txt_msk'] = self.token_dim_shape

        # mutable build-time state (reset per build)
        self.attention_idx = 0

    def dict(self) -> typing.Dict[str, typing.Any]:
        return self.__dict__

    def __str__(self):
        return str(self.__dict__)


def align_tensor_op(x: typing.Dict[str, typing.Any]) -> typing.List[typing.Any]:
    """Fixed input-tensor ordering (reference :375-384)."""
    tensors = []
    if 'frame' in x:
        tensors.extend([x['frame'], x['cat_mask_x'], x['cat_mask_y'],
                        x['vid_msk_src'], x['vid_msk_tgt']])
    if 'token_x' in x:
        tensors.extend([x['token_x'], x['token_y']])
    if 'txt_msk' in x:
        tensors.append(x['txt_msk'])
    return tensors


class BlockArgs:
    """(params, tensor, name_extras) bundle flowing through every layer fn
    (reference :387-419).  Note ``is_last`` is intentionally NOT propagated by
    __call__ — the reference's BlockArgs.__call__ constructs the copy without
    it, which silently disables scale_by_depth inside most layer bodies; we
    reproduce that behavior for loss parity."""

    def __init__(self, params: ModelParameter, tensor, name_extras: typing.List[str],
                 is_last: bool = False):
        self.params = params
        self.tensor = tensor
        self.name_extras = name_extras
        self.is_last = is_last

    def __call__(self, *args):
        new = BlockArgs(self.params, self.tensor, self.name_extras[:])
        for a in args:
            if isinstance(a, ModelParameter):
                new.params = a
            elif isinstance(a, (list, tuple)):
                new.name_extras = list(a)
            elif isinstance(a, str):
                new.name_extras.append(a)
            else:  # NamedTensor
                new.tensor = a
        return new

    def __iter__(self):
        yield from self.name_extras

    def __len__(self):
        return len(self.name_extras)

    def __getitem__(self, idx):
        return self.name_extras[idx]
