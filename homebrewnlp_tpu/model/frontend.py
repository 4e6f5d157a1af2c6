"""Layer registry + block string DSL (reference: /root/reference/src/model/frontend.py).

Block config strings like
``"attention-biased_attention_map-absolute-input_as_value-shared"`` are split
on '-' into the layer name + name_extras flags; ``split_path`` implements the
';'/',' add/multiply multi-branch DSL (frontend.py:39-55).
"""
from __future__ import annotations

from ..config import BlockArgs, BlockConfig, ModelParameter
from ..core import scope
from ..core.tensor import NamedTensor, add, multiply
from .activation import activate
from .backend import ConstantInit
from .basic import (bottleneck_group_linear, dropout, feed_forward,
                    feed_forward_product_key_memory, group_linear, mlp,
                    product_key_memory, reduced_half_linear, rezero, sum_heads,
                    transpose_sequence_features)
from .cca import cca
from .gated_delta import gated_delta
from .kda import kda
from .lightning import lightning
from .denoise import joined_tokens
from .loop import gated_loss
from .mtp import module_loss
from .mamba import mamba
from .moe import moe
from .normalization import norm
from .route import route_early
from .spatial import attention, cummean, cumsum


def convolution(args: BlockArgs) -> NamedTensor:
    """Causal conv over the current attention dim.

    The reference ships this layer disabled — its hand-written mtf
    Operation raises ``ValueError("Convolution is currently broken")``
    (/root/reference/src/model/convolution.py:129).  Here it works: a dense
    features→features convolution with kernel ``convolution_size`` over the
    round-robin attention axis, causal when that axis is in
    ``masked_attention_dimensions``, via lax.conv_general_dilated (MXU path).
    """
    import jax.lax
    import jax.numpy as jnp
    from ..core.dims import Dim, shape_size
    from ..core.tensor import nt, transpose_to
    from .backend import orthogonal_var
    from .utils import get_attention_dim, is_masked

    from . import decode as decode_mod

    params = args.params
    dim = get_attention_dim(args).dim
    masked = is_masked(args)
    state = decode_mod.active()
    decoding = decode_mod.is_decode_dim(state, dim)
    full_len = state.seq_len if decoding else dim.size
    kernel = min(params.convolution_size, full_len)
    feature_dims = list(params.feature_dims)
    kernel_dim_in = [Dim("_conv_in", shape_size(feature_dims))]
    canonical = [d for d in args.tensor.dims if d not in feature_dims and d != dim] \
        + [dim] + feature_dims
    x = transpose_to(args.tensor, canonical)
    lead = shape_size(canonical[:-1 - len(feature_dims)])
    features = shape_size(feature_dims)
    data = x.data.reshape(lead, dim.size, features)
    w = orthogonal_var(args, [Dim("_conv_k", kernel)] + kernel_dim_in
                       + feature_dims, kernel_dim_in)
    wdata = w.data.reshape(kernel, features, features)
    if decoding:
        if not masked:
            raise NotImplementedError("incremental decode needs causal conv")
        xw = decode_mod.rolling_window(
            nt(data, [Dim("_lead", lead), dim, Dim("_feat", features)]),
            dim, kernel)
        out = jnp.einsum("lkf,kfo->lo", xw.data, wdata)[:, None]
    else:
        pstate = decode_mod.prefill_active()
        if masked and decode_mod.is_prefill_dim(pstate, dim):
            decode_mod.prefill_store_convwin(
                nt(data, [Dim("_lead", lead), dim, Dim("_feat", features)]),
                dim, kernel)
        if masked:
            data = jnp.pad(data, ((0, 0), (kernel - 1, 0), (0, 0)))
            padding = "VALID"
        else:
            padding = "SAME"
        out = jax.lax.conv_general_dilated(
            data, wdata, window_strides=(1,), padding=padding,
            dimension_numbers=("NWC", "WIO", "NWC"))
    out = nt(out.reshape([d.size for d in canonical]).astype(args.tensor.dtype),
             canonical)
    return transpose_to(out, args.tensor.dims)


def _get_block_part(block_part_config: BlockConfig, params: ModelParameter,
                    block_input: NamedTensor) -> NamedTensor:
    out = block_input
    for idx, layer in enumerate(block_part_config.layer, 1):
        name, *extras = layer.split('-')
        args = BlockArgs(params, out, extras, idx == len(block_part_config.layer))
        out = scope.scoped(name + '_', LAYER_FUNCTIONS[name], args)
    if block_part_config.skip and block_part_config.merge == "scaled":
        if block_part_config.memory_reduction_strategy not in ("none",
                                                               "checkpoint"):
            raise NotImplementedError(
                "a block part's merge \"scaled\" under the revnet / momentum "
                "strategies (they own the residual themselves)")
        out = scope.scoped("merge_", scaled_merge, params, block_input, out)
    elif block_part_config.skip and block_part_config.memory_reduction_strategy in ("none", "checkpoint"):
        if params.residual_multiplier != 1:
            out = out * params.residual_multiplier
        out = out + block_input
    return out


def scaled_merge(params: ModelParameter, residual: NamedTensor,
                 out: NamedTensor) -> NamedTensor:
    """ZAYA1's scaled residual merge, ``(residual * a_r + b_r) + (out * a_o +
    b_o)``: four learned vectors over the features (``a`` = 1, ``b`` = 0 at
    initialisation, so it starts as ``residual + out``), created in the
    order a_r, b_r, a_o, b_o and read in float32; the sum is made in
    float32 and returned in the stream's dtype."""
    import jax
    import jax.numpy as jnp
    from ..core.tensor import nt, transpose_to
    from .recurrent import _small_var
    feats = list(params.feature_dims)
    args = BlockArgs(params, out, [])
    a_r, b_r, a_o, b_o = (
        _small_var(args, "constant_var", feats, ConstantInit(value))
        for value in (1.0, 0.0, 1.0, 0.0))
    dims = [d for d in residual.dims if d not in feats] + feats
    with jax.named_scope("merge"):
        merged = (transpose_to(residual, dims).data.astype(jnp.float32) * a_r
                  + b_r) \
            + (transpose_to(out, dims).data.astype(jnp.float32) * a_o + b_o)
        return transpose_to(nt(merged.astype(residual.dtype), dims),
                            residual.dims)


def block_part_fn(params: ModelParameter, block_part_config: BlockConfig,
                  block_input: NamedTensor, name_prefix: str = 'block') -> NamedTensor:
    return scope.scoped(f"{name_prefix}_", _get_block_part, block_part_config,
                        params, block_input)


def split_path(args: BlockArgs) -> NamedTensor:
    """';'-separated parallel branches combined by add/multiply."""
    base, *name_extras = '-'.join(args.name_extras).split(';')
    base = base.split('-')
    if 'add' in base:
        out, fn = 0, add
    elif 'multiply' in base:
        out, fn = 1, multiply
    else:
        raise ValueError(f"split_path needs add/multiply base, got {base}")
    for conf in name_extras:
        out = fn(out, _get_block_part(BlockConfig({'skip': False, 'layer': conf.split(',')}, ''),
                                      args.params, args.tensor))
    return out


LAYER_FUNCTIONS = {'feed_forward': feed_forward,
                   'attention': attention,
                   'cummean': cummean,
                   'cumsum': cumsum,
                   'norm': norm,
                   'rezero': rezero,
                   'activation': activate,
                   'convolution': convolution,
                   'dropout': dropout,
                   'group_linear': group_linear,
                   'split_path': split_path,
                   'feed_forward_product_key_memory': feed_forward_product_key_memory,
                   'product_key_memory': product_key_memory,
                   'reduced_half_linear': reduced_half_linear,
                   'transpose_sequence_features': transpose_sequence_features,
                   'bottleneck_group_linear': bottleneck_group_linear,
                   'sum_heads': sum_heads,
                   'moe': moe,
                   'mamba': mamba,
                   'gated_delta': gated_delta,
                   'kda': kda,
                   'mlp': mlp,
                   'cca': cca,
                   'lightning': lightning,
                   'route_early': route_early,
                   }

#: what declares itself (model/declare.py) beside the layers of the DSL: a
#: looped model's loss (model/loop.py), a multi-token-prediction module's
#: (model/mtp.py), block-diffusion training's noise (model/denoise.py)
DECLARING = (gated_loss, module_loss, joined_tokens)
