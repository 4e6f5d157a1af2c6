"""Model graph assembly (reference: /root/reference/src/model/__init__.py).

``build`` mirrors the reference's scoped _input/_body/_output/_loss pipeline
(:203-228): video patch/bit-unfold + empty-frame embeds, factorized-vocab text
embedding, depth × block_config body under a memory-reduction strategy, tied
token head einsum + sigmoid video head, softmax-xent with z-loss,
contrastive variants, L1 video loss, optional accuracy.

``Model`` packages the two-phase init/apply around it: init materialises
parameters and records the per-block parameter plan used by the reversible /
checkpointed body (model/blocks.py).
"""
from __future__ import annotations

import math
import time
import typing

import jax
import jax.numpy as jnp

from .. import telemetry
from ..config import BlockArgs, ModelParameter
from ..core import scope
from ..core.dims import Dim, shape_sub
from ..core.tensor import (NamedTensor, add_n, argmax, cast, concat,
                           dropout as tensor_dropout, einsum, equal,
                           nt, ones, reciprocal, reduce_sum, sigmoid, sign,
                           slice_, sqrt, square, weighted_add)
from ..core.value_pool import ValuePool
from .backend import linear_from_features, linear_to_features
from .blocks import BlockSpec, _merge_stats, run_body_blocks
from .denoise import joined_tokens, masked_loss, noised_half
from .embedding import batched_gather, embed, gather_embed
from .frontend import block_part_fn

class LossInfo(typing.NamedTuple):
    total_loss: typing.Any
    loss_list: list
    video_loss: typing.Any
    accuracy: typing.Any
    token_loss: typing.Any
    frame_out: typing.Any
    token_out: typing.Any
    #: {name: 1-D array over the layers that reported it} when
    #: ``Model.apply(layer_stats=True)`` ran the plain residual stream
    #: (layer moe's expert load; model/blocks.py), else None
    layer_stats: typing.Optional[dict] = None
    #: what the step differentiates where that is more than ``total_loss``
    #: (a multi-token-prediction module's loss at its weight on top,
    #: model/mtp.py: float32), else None
    objective: typing.Any = None


def _default_ones(params: ModelParameter, inp) -> NamedTensor:
    if inp is None:
        return ones([], params.calculation_dtype)
    return cast(inp, params.calculation_dtype)


def _input(params: ModelParameter, vid, cat_msk_src, txt_src, vid_msk_src,
           spatial_ctx: Dim, storage: dict):
    tgt = None
    src = None
    if params.use_video:
        base_args = BlockArgs(params, vid, [''])
        vid = cast(vid, params.calculation_dtype)
        vid = tensor_dropout(vid, params.train, 1 - params.input_dropout,
                             scope.current().next_rng())

        if params.use_bit_fold_input_pipeline:
            folded = cast(vid, jnp.int64)
            concat_list = []
            for unfold_idx in range(params.fold_count):
                part = (folded.data // ((2 ** params.bit_fold_value) ** unfold_idx)
                        ) % (2 ** params.bit_fold_value)
                concat_list.append(nt(part.astype(jnp.uint8), folded.dims))
            vid = concat(concat_list, 'color_channels')

        vid = cast(vid, params.calculation_dtype) / 255
        context_dimension = vid.dims[1]
        input_features = [vid.dims[-1]]
        # the reference's utils_slice unanonymizes after slicing, which renames
        # the '_sequence' input dim to 'sequence' (src/utils_mtf.py:336-351)
        from .utils import unanonymize
        tgt = unanonymize(slice_(vid, 1, context_dimension.size, context_dimension),
                          'sequence')
        src = unanonymize(slice_(vid, 0, context_dimension.size - 1, context_dimension),
                          'sequence')

        if params.empty_frame_embedding is not None:
            embed_args = base_args(params.empty_frame_embedding)
            src = weighted_add(src, embed(embed_args, list(vid.dims[2:])), vid_msk_src)
            src = weighted_add(src, embed(embed_args, list(vid.dims[2:])), cat_msk_src)

        src = linear_to_features(base_args(src), input_features)

        for config_idx, config in enumerate(params.input_block_config):
            src = block_part_fn(params, config, src, f'vid_inp{config_idx}')

    if params.use_language:
        if params.diffusion_block:
            # block-diffusion training: the noised sequence before the clean
            # one, ONE stream of 2 x sequence_length through the one table
            txt_src = joined_tokens(params, txt_src, storage)
        base_args = BlockArgs(params, txt_src, [''])
        intermediate = Dim(params.intermediate[0].name,
                           int(params.intermediate[0].size * params.vocab_weight_factorization))
        txt_args = base_args(txt_src, list(params.token_embedding))
        # vocab_weight_factorization 0: no narrow table and projection, one
        # row of all features a token, h = E[token]
        direct = not params.vocab_weight_factorization
        if direct and params.token_patch_size != 1:
            raise ValueError("vocab_weight_factorization 0 (a direct "
                             "embedding) needs token_patch_size 1")
        txt = gather_embed(
            txt_args, [params.vocab_dim] + (list(params.feature_dims)
                                            if direct else [intermediate]),
            storage=storage)
        txt = tensor_dropout(txt, params.train, 1 - params.input_dropout,
                             scope.current().next_rng())
        txt = reduce_sum(txt, reduced_dim=params.token_patch_dim) if direct \
            else linear_to_features(base_args(txt),
                                    [params.token_patch_dim, intermediate])
        if params.embedding_multiplier != 1:
            txt = txt * params.embedding_multiplier

        for config_idx, config in enumerate(params.input_block_config):
            txt = block_part_fn(params, config, txt, f'lang_inp{config_idx}')

    if params.use_video and params.use_language:
        # src: [batch, sequence, height_v, width?, feat...] / txt joins on the
        # spatial_ctx axis exactly as the reference concat (model/__init__.py:88)
        return concat([src, txt], spatial_ctx.name), tgt
    if not params.use_video:
        return txt, tgt
    return src, tgt


def _body(params: ModelParameter, src: NamedTensor,
          plan, loop_pass: int = 0) -> typing.Tuple[NamedTensor, tuple]:
    base_args = BlockArgs(params, src, [''])
    if params.use_initial_position_embedding:
        for dim in shape_sub(src.dims, params.feature_dims)[1:]:
            src = src + embed(base_args(list(params.position_embedding)),
                              [dim] + list(params.feature_dims))
    return run_body_blocks(params, src, plan, loop_pass)


def _output(params: ModelParameter, out: NamedTensor, spatial_ctx: Dim,
            storage: typing.Optional[dict] = None):
    """``storage`` (the build's own dict) receives ``head``: the head
    matmul's two operands, from which ``_loss`` takes the cross-entropy
    without the logits (model/loss.py).  ``token_out`` is made all the same;
    a jitted caller that does not read it never computes it."""
    base_args = BlockArgs(params, out, [''])
    token_out = frame_out = None

    contrastive = (params.contrastive_across_token_embeddings
                   or params.contrastive_across_samples)
    if params.use_language:
        token_out = slice_(out, 0, params.language_token_patch, spatial_ctx.name) \
            if params.use_video else out
        if params.diffusion_block:
            # the head and the loss read the noised half alone
            token_out = noised_half(params, token_out)
        if not contrastive:
            for config_idx, config in enumerate(params.output_block_config):
                token_out = block_part_fn(params, config, token_out, f'lang_out{config_idx}')
            if storage is not None:
                # what the output blocks leave: a looped model's next pass
                # starts from it (_build_looped)
                storage["stream"] = token_out
            new = [params.token_patch_dim, params.vocab_dim]
            old = list(params.feature_dims)
            if params.tie_word_embeddings:
                # the head IS the (direct) token embedding [vocab, features]:
                # one parameter, and autodiff adds the head's gradient to the
                # gather's
                table = storage["text_input_embedding"]
                emb = nt(table.data[:, None],
                         [table.dims[0], params.token_patch_dim]
                         + list(table.dims[1:]))
            else:
                emb = embed(base_args(list(params.output_embedding)),
                            old + new)
            if params.logits_scaling != 1:
                token_out = token_out * (1 / params.logits_scaling)
            if storage is not None:
                storage["head"] = (token_out, emb)
            token_out = einsum([token_out, emb],
                               output_shape=shape_sub(token_out.dims, old) + new)

    if params.use_video:
        frame_out = slice_(out, params.language_token_patch * params.use_language,
                           out.dim(spatial_ctx.name).size, spatial_ctx.name)
        for config_idx, config in enumerate(params.output_block_config):
            frame_out = block_part_fn(params, config, frame_out, f'vid_out{config_idx}')
        frame_out = sigmoid(linear_from_features(base_args(frame_out),
                                                 [params.color_channel_dim]))
    return frame_out, token_out


def softmax_cross_entropy_with_logits(params: ModelParameter,
                                      logits: NamedTensor,
                                      targets: NamedTensor,
                                      head: typing.Optional[NamedTensor] = None
                                      ) -> NamedTensor:
    """Mean softmax cross-entropy + z-loss (reference:
    src/mtf_wrapper.py:64-71) through ``model/loss.py``, which walks the
    sequence in chunks and holds neither a float32 ``[tokens, vocab]`` array
    beyond its chunk nor a one-hot of the targets.  With ``head`` (the
    output embedding) ``logits`` is the head's INPUT and the matmul is part
    of the walk.  Reported in the calculation dtype, as ever."""
    from ..core.tensor import transpose_to
    from .loss import head_xent
    seq = [d for d in targets.dims if d.name == params.sequence_dim.name]
    last = [params.token_patch_dim]
    lead = [d for d in targets.dims if d not in seq + last]
    inner = list(params.feature_dims) if head is not None \
        else last + [params.vocab_dim]

    def flat(t: NamedTensor, tail):
        tail = list(tail)
        data = transpose_to(t, lead + seq + tail).data
        return data.reshape((math.prod(d.size for d in lead),
                             math.prod(d.size for d in seq))
                            + tuple(d.size for d in tail))

    w = None if head is None else transpose_to(
        head, list(params.feature_dims) + last + [params.vocab_dim]).data
    loss = head_xent(flat(logits, inner), w, flat(targets, last),
                     params.z_loss)
    return nt(loss.astype(logits.dtype), ())


def _loss(params: ModelParameter, frame_out, token_out, txt_tgt, loss_list,
          vid_msk_tgt, cat_msk_tgt, vid_tgt, storage: dict):
    token_loss = accuracy = video_loss = None
    if params.use_language:
        if params.contrastive_across_samples or params.contrastive_across_token_embeddings:
            token_out = token_out / sqrt(reduce_sum(square(token_out),
                                                    reduced_dim=params.feature_dims))
        if params.contrastive_across_samples:
            sum_across_samples = reduce_sum(token_out, reduced_dim=params.sequence_dim)
            sum_across_batch = reduce_sum(token_out, reduced_dim=params.batch_dim)
            token_loss = einsum([sum_across_batch, sum_across_batch], []) / params.train_batch_size
            token_loss = token_loss - einsum([sum_across_samples, sum_across_samples],
                                             []) / params.sequence_length
            token_loss = token_loss / (params.train_batch_size * params.sequence_length)
        elif params.contrastive_across_token_embeddings:
            emb = storage['text_input_embedding']
            token_loss = einsum([token_out, emb], [])
            gathered = batched_gather(emb, txt_tgt, [params.head_dim])
            token_loss = token_loss - einsum([token_out, gathered], []) * 2
            token_loss = token_loss / (token_out.size * params.vocab_size)
        elif "denoise" in storage:
            # block-diffusion training: the masked positions alone, each at
            # 1 / its rate, against the SAME position's clean token
            token_loss = nt(masked_loss(params, *storage["head"], storage
                                        ).astype(token_out.dtype), ())
        elif "head" in storage:
            token_loss = softmax_cross_entropy_with_logits(
                params, storage["head"][0], txt_tgt, storage["head"][1])
        else:
            token_loss = softmax_cross_entropy_with_logits(params, token_out, txt_tgt)
        loss_list.append(token_loss)
        if params.calc_accuracy:
            acc = cast(equal(argmax(token_out, params.vocab_dim), txt_tgt),
                       params.calculation_dtype)
            accuracy = reduce_sum(acc, output_shape=[]) / txt_tgt.size

    if params.use_video:
        out = frame_out - vid_tgt
        video_loss = einsum([out, vid_msk_tgt, cat_msk_tgt,
                             nt(jnp.asarray(1 / frame_out.size,
                                            params.calculation_dtype), ()),
                             sign(out)], [])
        loss_list.append(video_loss)
        if vid_msk_tgt is not None:
            video_loss = einsum([nt(jnp.asarray(float(vid_msk_tgt.size),
                                                params.calculation_dtype), ()),
                                 reciprocal(reduce_sum(vid_msk_tgt)),
                                 nt(jnp.asarray(float(cat_msk_tgt.size),
                                                params.calculation_dtype), ()),
                                 reciprocal(reduce_sum(cat_msk_tgt)),
                                 video_loss], [])
    return loss_list, token_loss, accuracy, video_loss


def _build_looped(params: ModelParameter, src: NamedTensor, txt_tgt,
                  spatial_ctx: Dim, storage: dict, plan):
    """A looped model (``loop_steps`` > 1; model/loop.py): ``h_t =
    output blocks(body(h_(t-1)))`` for ``t = 1 .. loop_steps`` from ``h_0`` the
    embedding — the SAME blocks, parameters and positions every pass, the
    output blocks' result (the final norm's) the next pass's input and the
    head's — then the gate and the loss over all passes.

    The passes are a Python loop over the unrolled blocks: every pass opens
    scopes ``body`` and ``output`` AGAIN (core/scope.py), so the blocks
    resolve the parameters of the first, each block is a ``jax.checkpoint``
    region of its own a pass under ``checkpoint``, and autodiff adds a
    parameter's gradients over its uses.  A pass is one region ``loop/pass<t>``
    of the device trace (a scan over passes would fold the four into one).
    Init mode runs ONE pass — it makes each parameter once and records the
    one plan — and stands it in for the others, since init reads no value."""
    from .loop import gated_loss
    ctx = scope.current()
    init = ctx.mode == "init" or plan is None
    streams, token_out, first_plan = [], None, plan
    for step in range(params.loop_steps):
        if init and step:
            streams.append(streams[0])
            continue
        again = step > 0
        params.attention_idx = 0
        with jax.named_scope("loop"), jax.named_scope(f"pass{step}"):
            with scope.name_scope("body", again):
                out, made = _body(params, src, plan, step)
            with scope.name_scope("output", again):
                _, token_out = _output(params, out, spatial_ctx, storage)
        if not step:
            first_plan = made
        src = storage["stream"]
        streams.append(src)
    with scope.name_scope("loss"):
        total, cross, stats = gated_loss(params, streams, storage["head"][1],
                                         txt_tgt)
    if ctx.layer_stats is not None:
        ctx.layer_stats.append(stats)
    params.attention_idx = 0
    total, cross = nt(total, ()), nt(cross, ())
    return LossInfo(total, [total], None, None, cross, None,
                    token_out), first_plan


def _build(params: ModelParameter, vid, cat_msk_src, cat_msk_tgt, txt_src,
           txt_tgt, vid_msk_src, vid_msk_tgt, txt_msk, plan):
    cat_msk_src = _default_ones(params, cat_msk_src) if params.use_video else cat_msk_src
    cat_msk_tgt = _default_ones(params, cat_msk_tgt) if params.use_video else cat_msk_tgt
    vid_msk_src = _default_ones(params, vid_msk_src) if params.use_video else vid_msk_src
    vid_msk_tgt = _default_ones(params, vid_msk_tgt) if params.use_video else vid_msk_tgt

    loss_list: list = []
    spatial_ctx: Dim = txt_tgt.dims[-2] if params.use_language else vid.dims[2]
    storage: dict = {}

    src, vid_tgt = scope.scoped("input", _input, params, vid, cat_msk_src,
                                txt_src, vid_msk_src, spatial_ctx, storage)
    if params.loop_steps > 1:
        return _build_looped(params, src, txt_tgt, spatial_ctx, storage, plan)
    # a multi-token-prediction module's blocks follow the body's in the plan
    blocks = params.depth * len(params.block_config)
    out, body_plan = scope.scoped("body", _body, params, src,
                                  None if plan is None else plan[:blocks])
    frame_out, token_out = scope.scoped("output", _output, params, out,
                                        spatial_ctx, storage)
    loss_list, token_loss, accuracy, video_loss = scope.scoped(
        "loss", _loss, params, frame_out, token_out, txt_tgt, loss_list,
        vid_msk_tgt, cat_msk_tgt, vid_tgt, storage)
    info = LossInfo(add_n(loss_list), loss_list, video_loss, accuracy,
                    token_loss, frame_out, token_out)
    if params.mtp_depth:
        from .mtp import module_loss
        mtp_loss, mtp_plan = scope.scoped(
            "mtp", module_loss, params, storage, txt_tgt, token_loss,
            None if plan is None else plan[blocks:])
        body_plan += mtp_plan
        info = info._replace(objective=nt(
            info.total_loss.data.astype(jnp.float32)
            + params.mtp_loss_weight * mtp_loss, ()))

    params.attention_idx = 0
    return info, body_plan


def build(params: ModelParameter, vid, cat_msk_src, cat_msk_tgt, txt_src,
          txt_tgt, vid_msk_src, vid_msk_tgt, txt_msk, plan=None):
    return scope.scoped(params.model_mode, _build, params, vid, cat_msk_src,
                        cat_msk_tgt, txt_src, txt_tgt, vid_msk_src,
                        vid_msk_tgt, txt_msk, plan)


def _refuse_looped(params: ModelParameter, what: str) -> None:
    if params.loop_steps > 1:
        raise NotImplementedError(
            f"{what} of a looped model (loop_steps {params.loop_steps}): the "
            "passes' KV caches and the exit by threshold are not built; a "
            "looped model trains and runs its full forward only")
    if params.mtp_depth:
        raise NotImplementedError(
            f"{what} of a model with a multi-token-prediction module "
            f"(mtp_depth {params.mtp_depth}, model/mtp.py): the module as a "
            "self-drafting head is not built; it trains and runs its full "
            "forward only")
    if params.diffusion_block:
        raise NotImplementedError(
            f"{what} of a block-diffusion model (diffusion_block "
            f"{params.diffusion_block}, model/denoise.py): a sampler whose "
            "step fills a block in several passes and a cache written a "
            "clean block at a time are not built; it trains and runs its "
            "full forward only")


class Model:
    """Two-phase wrapper: ``init`` materialises params + block plan,
    ``apply`` is a pure function of (params, inputs) suitable for jit/grad."""

    def __init__(self, params: ModelParameter):
        self.params = params
        self.plan: typing.Optional[typing.Tuple[BlockSpec, ...]] = None
        self.param_dims: typing.Dict[str, tuple] = {}
        # contracted-dim names per parameter (core/scope.py param_fan_in);
        # serving quantization's safe scale axes
        self.param_fan_in: typing.Dict[str, tuple] = {}

    def _named_inputs(self, batch: typing.Dict[str, jax.Array]):
        p = self.params
        def get(key, dims):
            if key not in batch or batch[key] is None:
                return None
            return nt(batch[key], dims)
        vid = get('frame', p.frame_input_shape) if p.use_video else None
        token_x = get('token_x', p.token_dim_shape) if p.use_language else None
        token_y = get('token_y', p.token_dim_shape) if p.use_language else None
        cat_msk_x = get('cat_mask_x', p.frame_mask_shape) if p.use_video else None
        cat_msk_y = get('cat_mask_y', p.frame_mask_shape) if p.use_video else None
        vid_msk_src = get('vid_msk_src', p.frame_mask_shape) if p.use_video else None
        vid_msk_tgt = get('vid_msk_tgt', p.frame_mask_shape) if p.use_video else None
        txt_msk = get('txt_msk', p.token_dim_shape) if p.use_language else None
        return vid, cat_msk_x, cat_msk_y, token_x, token_y, vid_msk_src, vid_msk_tgt, txt_msk

    def init(self, batch: typing.Dict[str, jax.Array], seed: typing.Optional[int] = None
             ) -> typing.Dict[str, jax.Array]:
        """Materialise parameters (host numpy) and the block plan.

        The forward pass is traced abstractly (eval_shape) so init performs
        no device computation at all — parameters are numpy master copies;
        the trainer device_puts them with their NamedShardings.  The walk
        only names each value; a pool of host threads makes them meanwhile
        (core/value_pool.py), and init returns when the last one is stored.
        """
        ctx = scope.Context("init", seed=self.params.data_seed if seed is None else seed,
                            record_touched=True)

        def _run(abstract_batch):
            with scope.context(ctx):
                args = self._named_inputs(abstract_batch)
                self.params.attention_idx = 0
                info, self.plan = build(self.params, *args, plan=None)
            return info.total_loss

        # once a run: the graph walk in init mode, then the wait for the
        # values still being made — hbnlp_init_values_seconds_total, in wall
        # seconds inside the span, so span - counter stays the walk
        with telemetry.span("setup/model_init"), ValuePool() as pool:
            ctx.value_pool = pool
            jax.eval_shape(_run, {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                                  for k, v in batch.items() if v is not None})
            t0 = time.monotonic()
            ctx.params.update(pool.finish())
            waited = time.monotonic() - t0
        r = telemetry.registry()
        r.counter("hbnlp_init_values_seconds_total",
                  "wall seconds Model.init waited for parameter values "
                  "after its graph walk").inc(waited)
        r.counter("hbnlp_init_values_cpu_seconds_total",
                  "thread CPU seconds in parameter initializers and casts, "
                  "over all workers").inc(pool.cpu_seconds)
        r.gauge("hbnlp_init_workers",
                "threads Model.init made its values on (1 = one after "
                "another)").set(pool.workers)
        self.param_dims = dict(ctx.param_dims)
        self.param_fan_in = dict(ctx.param_fan_in)
        return ctx.params

    def apply(self, variables: typing.Dict[str, jax.Array],
              batch: typing.Dict[str, jax.Array],
              rng: typing.Optional[jax.Array] = None,
              mesh: typing.Any = None,
              stats_sink: typing.Optional[list] = None,
              layer_stats: bool = False) -> LossInfo:
        assert self.plan is not None, "call init() first (or assign .plan)"
        ctx = scope.Context("apply", params=variables, rng_key=rng, mesh=mesh)
        ctx.quant_scales = getattr(self, "quant_scales", None)
        ctx.stats_sink = stats_sink
        if layer_stats:
            ctx.layer_stats = []
        with scope.context(ctx):
            args = self._named_inputs(batch)
            self.params.attention_idx = 0
            info, _ = build(self.params, *args, plan=self.plan)
        if ctx.layer_stats:
            info = info._replace(layer_stats=_merge_stats(ctx.layer_stats))
        return info

    def train_grads_1f1b(self, variables: typing.Dict[str, jax.Array],
                         batch: typing.Dict[str, jax.Array],
                         rng: typing.Optional[jax.Array],
                         mesh) -> typing.Tuple[typing.Dict[str, jax.Array],
                                               LossInfo]:
        """Loss + gradients via the fused 1F1B pipeline schedule
        (parallel/pipeline_1f1b.py): the body runs the per-tick
        forward/backward table with the output head + loss inside the last
        stage; the input embedding and its gradients run outside through an
        ordinary ``jax.vjp``.  Text models with the linear loss only."""
        from ..parallel.pipeline_1f1b import pipeline_train_1f1b

        p = self.params
        assert self.plan is not None, "call init() first (or assign .plan)"
        assert p.use_language and not p.use_video, \
            "1f1b pipeline supports text (gpt) mode only"
        assert not (p.contrastive_across_samples
                    or p.contrastive_across_token_embeddings), \
            "1f1b pipeline supports the plain xent loss only"
        from ..core import sharding as shardlib
        n_micro = max(1, int(p.pipeline_microbatches
                             or mesh.shape[shardlib.PIPE_AXIS]))
        if p.train_batch_size % n_micro:
            raise ValueError(f"batch {p.train_batch_size} not divisible by "
                             f"pipeline_microbatches={n_micro}")

        ctx = scope.Context("apply", params=variables, rng_key=rng, mesh=mesh)
        with scope.context(ctx):
            (_, _, _, txt_src, txt_tgt, _, _, _) = self._named_inputs(batch)
            p.attention_idx = 0
            mode_frame = ctx.enter(p.model_mode)          # e.g. "gpt0"
            spatial_ctx: Dim = txt_tgt.dims[-2]
            input_names = [n for n in variables
                           if n.startswith(f"{mode_frame}/input")]
            head_names = [n for n in variables
                          if n.startswith((f"{mode_frame}/output",
                                           f"{mode_frame}/loss"))]

            src_dims_box = []

            def input_f(sub):
                c = scope.Context("apply", params={**variables, **sub},
                                  rng_key=rng, mesh=mesh)
                c.stack.append(scope._Frame(mode_frame))
                with scope.context(c):
                    src, _ = scope.scoped("input", _input, p, None, None,
                                          txt_src, None, spatial_ctx, {})
                src_dims_box.append(src.dims)
                return src.data

            src_data, input_vjp = jax.vjp(
                input_f, {n: variables[n] for n in input_names})
            src_nt = nt(src_data, src_dims_box[0])

            # body blocks exactly as run_body_blocks builds them
            ctx.enter("body")
            prefix = tuple(f.name for f in ctx.stack[1:])
            from .blocks import ReplayBlock
            blocks = [(i, c, bc) for i in range(p.depth)
                      for c, bc in enumerate(p.block_config)]
            fns, subsets = [], []
            attn_idx = 0
            for (i, c, bc), (_, _, names) in zip(blocks, self.plan):
                fns.append(ReplayBlock(p, bc, i, c, prefix, attn_idx))
                attn_idx += sum(layer.split('-')[0] == "attention"
                                for layer in bc.layer)
                subsets.append({n: variables[n] for n in names})
            ctx.exit()
            ctx.exit()  # mode frame

            mb = p.train_batch_size // n_micro
            src_dims_mb = (Dim(src_nt.dims[0].name, mb),) + tuple(src_nt.dims[1:])
            tgt_dims_mb = (Dim(txt_tgt.dims[0].name, mb),) + tuple(txt_tgt.dims[1:])

            def head_fn(head_sub, y_comb, tgt_data):
                c = scope.Context("apply", params={**variables, **head_sub},
                                  rng_key=rng, mesh=None)
                c.stack.append(scope._Frame(mode_frame))
                with scope.context(c):
                    out_nt = nt(y_comb, src_dims_mb)
                    tgt_nt = nt(tgt_data, tgt_dims_mb)
                    frame_out, token_out = scope.scoped("output", _output, p,
                                                        out_nt, spatial_ctx)
                    loss_list, token_loss, accuracy, _ = scope.scoped(
                        "loss", _loss, p, frame_out, token_out, tgt_nt, [],
                        None, None, None, {})
                total = add_n(loss_list).data
                acc = accuracy.data if accuracy is not None else jnp.zeros(())
                aux = jnp.stack([token_loss.data.astype(jnp.float32),
                                 acc.astype(jnp.float32)])
                return total, aux

            tgt_mb = txt_tgt.data.reshape((n_micro, mb)
                                          + txt_tgt.data.shape[1:])
            loss, aux, body_grads, head_grads, d_src = pipeline_train_1f1b(
                p, mesh, fns, subsets, self.plan, src_nt, tgt_mb, head_fn,
                {n: variables[n] for n in head_names}, 2,
                p.memory_reduction_strategy)
            (d_input,) = input_vjp(d_src.data)
            p.attention_idx = 0

        grads = dict(body_grads)
        for n, g in head_grads.items():
            grads[n] = g.astype(variables[n].dtype)
        for n, g in d_input.items():
            grads[n] = g
        for n in variables:
            grads.setdefault(n, jnp.zeros_like(variables[n]))
        loss_nt = nt(loss, ())
        info = LossInfo(loss_nt, [loss_nt], None, nt(aux[1], ()),
                        nt(aux[0], ()), None, None)
        return grads, info

    def apply_decode(self, variables: typing.Dict[str, jax.Array],
                     token_slice: jax.Array, pos: jax.Array,
                     caches: typing.Dict[str, jax.Array],
                     mesh: typing.Any = None
                     ) -> typing.Tuple[jax.Array, typing.Dict[str, jax.Array]]:
        """One incremental-decode step (model/decode.py).

        ``token_slice``: the input tokens at ``pos``, shaped like token_x
        with the sequence axis of length ``width`` (1 for every classic
        sampler; the speculative VERIFY step passes ``k + 1`` consecutive
        tokens per row and scores all of them in this one call — the width
        is inferred from the slice shape).  Returns (next-token logits at
        ``pos .. pos + width - 1`` as [batch, width, token_patch, vocab],
        updated caches).  Replaces the reference sampler's full forward per
        token (/root/reference/src/run/inference.py:76-97) with
        O(width)-per-step compute; only valid for causal text models
        (use_video off).
        """
        from .decode import DecodeState
        assert self.plan is not None, "call init() first (or assign .plan)"
        p = self.params
        _refuse_looped(p, "incremental decode")
        assert not p.use_video and p.use_language, \
            "incremental decode supports text (gpt) mode only"
        width = int(token_slice.shape[1])
        assert width < p.sequence_dim.size, \
            "decode slice must be narrower than the sequence (use apply)"
        state = DecodeState(jnp.asarray(pos, jnp.int32), p.sequence_dim.size,
                            p.sequence_dim.name, caches,
                            cache_dtype=p.decode_cache_dtype, model_params=p,
                            width=width)
        ctx = scope.Context("apply", params=variables, mesh=mesh, decode=state)
        ctx.quant_scales = getattr(self, "quant_scales", None)
        decode_dims = [Dim(d.name, width)
                       if d.name == p.sequence_dim.name else d
                       for d in p.token_dim_shape]
        with scope.context(ctx):
            tok = nt(token_slice, decode_dims)
            tgt = nt(jnp.zeros_like(token_slice), decode_dims)
            self.params.attention_idx = 0
            info, _ = build(p, None, None, None, tok, tgt, None, None, None,
                            plan=self.plan)
        return info.token_out.data, state.out

    def apply_prefill(self, variables: typing.Dict[str, jax.Array],
                      token_x: jax.Array, n: jax.Array,
                      mesh: typing.Any = None) -> typing.Dict[str, jax.Array]:
        """Capture the decode caches for prompt positions in ONE forward.

        Returns the cache pytree equivalent to having run decode steps
        ``0..n-1`` of ``apply_decode`` (model/decode.py ``PrefillState``
        documents the per-cache argument), so the sampler can start its
        while_loop at ``q = n`` instead of walking the prompt one model call
        per token.  The full forward runs the normal (fastest) code paths —
        flash kernels, depth scan — with the capture hooks riding along.
        """
        from .decode import PrefillState
        assert self.plan is not None, "call init() first (or assign .plan)"
        p = self.params
        _refuse_looped(p, "prefill")
        assert not p.use_video and p.use_language, \
            "prefill supports text (gpt) mode only"
        from ..core import sharding as shardlib
        if mesh is not None \
                and getattr(mesh, "shape", {}).get(shardlib.SEQUENCE_AXIS, 1) > 1:
            raise ValueError("prefill needs the serving mesh (sequence axis "
                             "folded into data); got a sequence-sharded mesh")
        state = PrefillState(jnp.asarray(n, jnp.int32), p.sequence_dim.size,
                             p.sequence_dim.name,
                             cache_dtype=p.decode_cache_dtype, model_params=p)
        ctx = scope.Context("apply", params=variables, mesh=mesh)
        ctx.quant_scales = getattr(self, "quant_scales", None)
        ctx.prefill = state

        def _output_blocks(params, out):
            # output_block_config layers may create caches too (e.g. a
            # cumsum head block) — run them under the same "output" frame
            # _build opens so their cache names match the decode build;
            # contrastive configs skip them there as well
            if (params.contrastive_across_token_embeddings
                    or params.contrastive_across_samples):
                return
            token_out = out
            for config_idx, config in enumerate(params.output_block_config):
                token_out = block_part_fn(params, config, token_out,
                                          f'lang_out{config_idx}')

        def _prefill_forward(params, tok):
            # same scope frames _build opens, minus the vocab projection and
            # loss: the [b, s, patch, vocab] logits would be computed only
            # to be discarded — at BPE vocab sizes a significant share of
            # prefill FLOPs and HBM — and neither creates caches
            spatial_ctx: Dim = tok.dims[-2]
            src, _ = scope.scoped("input", _input, params, None, None, tok,
                                  None, spatial_ctx, {})
            out, _ = scope.scoped("body", _body, params, src, self.plan)
            scope.scoped("output", _output_blocks, params, out)
            params.attention_idx = 0

        with scope.context(ctx):
            tok = nt(token_x, p.token_dim_shape)
            self.params.attention_idx = 0
            scope.scoped(p.model_mode, _prefill_forward, p, tok)
        return state.out
