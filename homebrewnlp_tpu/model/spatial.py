"""Attention & spatial mixing (reference: /root/reference/src/model/spatial.py).

Generic attention over the "current" attention dim — round-robin over all
non-feature axes (multi-axis time/height/width attention for video).  Flags:
dot_product, embedded/positional/context keys, biased_softmax,
biased_attention_map, scale_attention_map, input_as_value, shared_key_value.
Causal masking via compare_range + -2e38 bias on dims listed in
masked_attention_dimensions.  cumsum/cummean are linear-time token mixers
(native AD replaces the reference's hand-written cumsum gradient).
"""
from __future__ import annotations

import typing

import jax
import numpy as np

from ..config import BlockArgs
from ..core.dims import Dim, shape_sub
from ..core import sharding as shardlib
from ..core.stash import stash_channel
from ..core.tensor import (NamedTensor, cumsum as tensor_cumsum, einsum, exp,
                           less, multiply, range_, reduce_max, reduce_sum,
                           stop_gradient, greater_equal)
from ..parallel.flash_attention import (SAVED_NAMES, SELECT_NAME,
                                        backward_form, band_applies,
                                        block_diffusion_scored_over_live,
                                        call_tiles, scored_over_live,
                                        stepped_applies)
from . import decode as decode_mod
from .basic import activated_linear_in, activated_linear_out
from .declare import Fact, Layer, Offer, Stat, step_offers
from .embedding import embed
from .utils import (anonymize, compare_range, get_attention_dim,
                    is_masked, linear_shapes)


def _key_dim(dim: Dim) -> Dim:
    """Anonymized key-position dim; full-length under incremental decode."""
    return decode_mod.key_dim_for(decode_mod.active(), dim)


def _anonymize_kv(x: NamedTensor, dim: Dim) -> NamedTensor:
    """anonymize() at train time; KV-cache scatter at decode time; at
    prefill time additionally capture the full-length tensor into the cache
    the decode steps would have filled (model/decode.py PrefillState)."""
    state = decode_mod.active()
    if decode_mod.is_decode_dim(state, dim):
        return decode_mod.spread(x, dim)
    pstate = decode_mod.prefill_active()
    if decode_mod.is_prefill_dim(pstate, dim):
        decode_mod.prefill_store_kv(x, dim)
    return anonymize(x, dim)


#: flags under which only the dense einsum reproduces the reference: the
#: map-bias flags need the dense [s, s] map, shared_key_value leaves the value
#: on the query dim
_DENSE_ONLY = ("biased_softmax", "biased_attention_map",
               "scale_attention_map", "shared_key_value")


def _plain_softmax_qkv(args: BlockArgs, dim: Dim, qry: NamedTensor,
                       key: typing.Union[NamedTensor, int], base: BlockArgs):
    """Shared gate + extraction for the ring/flash kernel routes.

    Returns (q, k, v, canonical, shp) — arrays reshaped to
    [lead-dims-folded, dim, heads, features] — or None when only the dense
    einsum reproduces the reference semantics: map-bias flags need the dense
    [s, s] map, and shared_key_value leaves the value on the QUERY dim so the
    reference contraction degenerates to val*rowsum(p) (spatial.py:60-66).
    The parameter-creation order (key, qry, val) matches the dense path so
    init (meshless) and kernel-routed apply resolve identical names."""
    from ..core.tensor import transpose_to
    params = args.params
    if any(f in args.name_extras for f in _DENSE_ONLY):
        return None
    if not isinstance(key, NamedTensor):
        return None
    if "input_as_value" in args.name_extras:
        val = args.tensor
    else:
        val = activated_linear_out(base)
    pstate = decode_mod.prefill_active()
    if decode_mod.is_prefill_dim(pstate, dim):
        # the kernel routes skip the dense path's _anonymize_kv sites, so
        # capture here — same order (key, then val) and the same PRE-broadcast
        # tensors, so the cache names, shapes, and values match the decode
        # build exactly
        decode_mod.prefill_store_kv(key, dim)
        decode_mod.prefill_store_kv(val, dim)
    canonical = [d for d in args.tensor.dims
                 if d not in (dim, params.head_dim, params.key_dim)] \
        + [dim, params.head_dim, params.key_dim]
    q = transpose_to(qry, canonical)
    # key may lack batch dims (positional embeds): broadcast via + 0*q
    k = transpose_to(key + 0 * qry, canonical)
    v = transpose_to(val + 0 * qry, canonical)
    bsz = 1
    for d in canonical[:-3]:
        bsz *= d.size
    shp = (bsz, dim.size, params.head_dim.size, params.key_dim.size)
    return (q.data.reshape(shp), k.data.reshape(shp), v.data.reshape(shp),
            canonical, shp)


def _maybe_ring_attention(args: BlockArgs, dim: Dim, qry: NamedTensor,
                          key: typing.Union[NamedTensor, int],
                          base: BlockArgs) -> typing.Optional[NamedTensor]:
    """Route dot-product attention over a sequence-sharded mesh through ring
    attention (parallel/ring_attention.py); plain softmax attention on the
    'sequence' dim only."""
    from ..core import scope as scope_mod
    from ..core.tensor import nt, transpose_to
    ctx = scope_mod.current()
    mesh = ctx.mesh
    if ctx.decode is not None:
        return None
    if (mesh is None
            or shardlib.SEQUENCE_AXIS not in getattr(mesh, "axis_names", ())
            or mesh.shape[shardlib.SEQUENCE_AXIS] <= 1
            or dim.name != "sequence"):
        return None
    qkv = _plain_softmax_qkv(args, dim, qry, key, base)
    if qkv is None:
        return None
    q, k, v, canonical, _ = qkv
    from ..parallel.ring_attention import ring_attention

    # causal=True always: the dense softmax branch masks unconditionally
    # (reference spatial.py:68), regardless of masked_attention_dimensions.
    # stash: the strategy machinery's replay stash channel
    # (model/blocks.py) — the zigzag ring collects/provides (out, lse) so
    # the strategy backward's recompute skips the whole ring
    out = ring_attention(q, k, v, mesh, causal=True,
                         scale=1.0,  # qry already carries the reference scale
                         stash=stash_channel(ctx, "attention"))
    out_nt = nt(out.reshape([d.size for d in canonical]), canonical)
    return transpose_to(out_nt, args.tensor.dims)


def _maybe_flash_attention(args: BlockArgs, dim: Dim, qry: NamedTensor,
                           key: typing.Union[NamedTensor, int],
                           base: BlockArgs) -> typing.Optional[NamedTensor]:
    """Route plain softmax dot-product attention through the pallas flash
    kernel (parallel/flash_attention.py): blockwise online softmax so the
    [s, s] score matrix never hits HBM.  On a data x model mesh the kernel
    runs per-device under shard_map (batch on 'data', heads on 'model';
    sequence is unsharded so local causality is global causality); the
    sequence- and pipe-sharded cases use ring attention / the dense path.
    Any other spatial dims fold into the batch, so multi-axis (video)
    attention uses it too.  Map-bias flags need the dense [s, s] map and
    fall through."""
    from ..core import scope as scope_mod
    from ..core.tensor import nt, transpose_to
    ctx = scope_mod.current()
    mesh = ctx.mesh
    if ctx.decode is not None:
        return None
    if not args.params.use_flash_attention:
        return None
    if mesh is not None and (mesh.shape.get(shardlib.SEQUENCE_AXIS, 1) > 1
                             or mesh.shape.get(shardlib.PIPE_AXIS, 1) > 1):
        return None
    if mesh is not None:
        # shard-divisibility gate BEFORE extracting qkv: _plain_softmax_qkv
        # consumes scoped parameter counters (and, under prefill, the kv
        # cache name counters), so bailing after it would leave the dense
        # fallback resolving drifted names — params that init never created,
        # and duplicate prefill captures
        lead = 1
        for d in args.tensor.dims:
            if d not in (dim, args.params.head_dim, args.params.key_dim):
                lead *= d.size
        if (lead % max(1, mesh.shape.get(shardlib.DATA_AXIS, 1))
                or args.params.head_dim.size
                % max(1, mesh.shape.get(shardlib.MODEL_AXIS, 1))):
            return None
    qkv = _plain_softmax_qkv(args, dim, qry, key, base)
    if qkv is None:
        return None
    q, k, v, canonical, shp = qkv
    # scale 1.0: qry already carries the reference scale
    out = _flash(ctx, q, k, v, 1.0)
    out_nt = nt(out.reshape([d.size for d in canonical]), canonical)
    return transpose_to(out_nt, args.tensor.dims)


def _flash(ctx, q, k, v, scale: float, window=None):
    """Causal flash attention on ``[lead, seq, heads, features]`` arrays: the
    kernel itself single-device, per device under shard_map on a data x model
    mesh (batch on 'data', heads on 'model'; the sequence is whole, so local
    causality is global causality).  ``window``: the kernels' own."""
    from ..parallel.flash_attention import attention as flash
    mesh = ctx.mesh
    if mesh is None:
        # causal=True always: the dense softmax branch masks unconditionally.
        # stash: the strategy machinery's stash channel (model/blocks.py:
        # collect / provide under revnet and momentum, "name" under
        # checkpoint) — single-device path only; the shard_map branch keeps
        # the plain kernel
        return flash(q, k, v, scale=scale, causal=True,
                     stash=stash_channel(ctx, "attention"), window=window)
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    spec = P(shardlib.DATA_AXIS if shardlib.DATA_AXIS in mesh.axis_names
             else None, None,
             shardlib.MODEL_AXIS if shardlib.MODEL_AXIS in mesh.axis_names
             else None, None)
    return shard_map(
        lambda q_, k_, v_: flash(q_, k_, v_, scale=scale, causal=True,
                                 window=window),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)(q, k, v)


def _masked_map(args: BlockArgs) -> typing.Tuple[NamedTensor, typing.Union[NamedTensor, int]]:
    dim = get_attention_dim(args).dim
    tmp = _key_dim(dim)
    bias = embed(args, [args.params.head_dim, dim, tmp])
    return bias, (compare_range(args.params, dim, tmp, greater_equal)
                  if is_masked(args) else 1)


_MAP_MIXER_FALLBACK_SEEN: typing.Set[str] = set()


def _map_mixer_declined(reason: str) -> None:
    """Loud, once per reason per process: the learned-map mixer expected the
    pallas blocked kernel (the default at supported shapes) but is taking
    the dense einsum."""
    if reason not in _MAP_MIXER_FALLBACK_SEEN:
        _MAP_MIXER_FALLBACK_SEEN.add(reason)
        print(f"map-mixer kernel fallback: {reason}; using the dense einsum",
              flush=True)


def _maybe_map_mixer(args: BlockArgs, dim: Dim, bias: NamedTensor,
                     mask: typing.Union[NamedTensor, int],
                     base: typing.Optional[BlockArgs]
                     ) -> typing.Optional[NamedTensor]:
    """Route the PURE learned-map mixer (biased_attention_map without
    dot_product/softmax: out = (bias·mask) @ value) through the pallas
    blocked kernel (parallel/map_mixer.py) — the flagship mixer's hot op.
    Returns None to fall back to the dense einsum; unsupported-shape
    declines are loud (``_map_mixer_declined``), semantically-different
    flag combinations (a second dense map) fall through silently.

    Same gate discipline as the flash route: every decline happens BEFORE
    value extraction, which consumes scoped parameter counters (and, under
    prefill, kv-cache name counters) exactly once on the taken path."""
    from ..core import scope as scope_mod
    from ..core.tensor import nt, transpose_to
    params = args.params
    if not params.use_map_mixer_kernel:
        return None
    if "scale_attention_map" in args.name_extras:
        return None  # a second dense map multiplies the output elementwise
    ctx = scope_mod.current()
    if ctx.decode is not None:
        _map_mixer_declined("incremental decode uses the kv-cache dense "
                            "path")
        return None
    if decode_mod.is_prefill_dim(decode_mod.prefill_active(), dim):
        _map_mixer_declined("prefill keeps the dense path (bit-parity with "
                            "the decode steps that continue its caches)")
        return None
    if params.head_dim not in args.tensor.dims \
            or params.key_dim not in args.tensor.dims:
        _map_mixer_declined("mixer tensor lacks the head/feature dims")
        return None
    tmp = _key_dim(dim)
    if dim.size != tmp.size or dim.size % 128:
        _map_mixer_declined(
            f"map is [{dim.size}, {tmp.size}] — kernel tiles need a square "
            "map on a 128-multiple sequence")
        return None
    mesh = ctx.mesh
    if mesh is not None and (mesh.shape.get(shardlib.SEQUENCE_AXIS, 1) > 1
                             or mesh.shape.get(shardlib.PIPE_AXIS, 1) > 1):
        _map_mixer_declined("sequence-/pipe-sharded meshes keep the dense "
                            "path (the learned map is not ring-decomposed)")
        return None
    lead = 1
    for d in args.tensor.dims:
        if d not in (dim, params.head_dim, params.key_dim):
            lead *= d.size
    if mesh is not None and (
            lead % max(1, mesh.shape.get(shardlib.DATA_AXIS, 1))
            or params.head_dim.size
            % max(1, mesh.shape.get(shardlib.MODEL_AXIS, 1))):
        _map_mixer_declined("lead/head dims not divisible by the data/model "
                            "mesh axes")
        return None
    val = (args.tensor if "input_as_value" in args.name_extras
           else activated_linear_out(base))
    canonical = [d for d in args.tensor.dims
                 if d not in (dim, params.head_dim, params.key_dim)] \
        + [dim, params.head_dim, params.key_dim]
    v4 = transpose_to(val + 0 * args.tensor, canonical)
    shp = (lead, dim.size, params.head_dim.size, params.key_dim.size)
    v_arr = v4.data.reshape(shp)
    bias_arr = transpose_to(bias, [params.head_dim, dim, tmp]).data
    causal = isinstance(mask, NamedTensor)
    from ..parallel.map_mixer import mix

    if mesh is None:
        out = mix(bias_arr, v_arr, causal=causal)
    else:
        from jax.sharding import PartitionSpec as P

        from jax import shard_map
        spec_v = P(shardlib.DATA_AXIS if shardlib.DATA_AXIS
                   in mesh.axis_names else None, None,
                   shardlib.MODEL_AXIS if shardlib.MODEL_AXIS
                   in mesh.axis_names else None, None)
        spec_b = P(shardlib.MODEL_AXIS if shardlib.MODEL_AXIS
                   in mesh.axis_names else None, None, None)
        out = shard_map(
            lambda b_, v_: mix(b_, v_, causal=causal),
            mesh=mesh, in_specs=(spec_b, spec_v), out_specs=spec_v,
            check_vma=False)(bias_arr, v_arr)
    out_nt = nt(out.reshape([d.size for d in canonical]), canonical)
    return transpose_to(out_nt, args.tensor.dims)


def cumsum(args: BlockArgs) -> NamedTensor:
    dim = get_attention_dim(args).dim
    state = decode_mod.active()
    if decode_mod.is_decode_dim(state, dim):
        return decode_mod.running_sum(args.tensor)
    out = tensor_cumsum(args.tensor, dim)
    pstate = decode_mod.prefill_active()
    if decode_mod.is_prefill_dim(pstate, dim):
        decode_mod.prefill_store_cumsum(out, dim)
    return out


def cummean(args: BlockArgs) -> NamedTensor:
    dim = get_attention_dim(args).dim
    state = decode_mod.active()
    if decode_mod.is_decode_dim(state, dim):
        import jax.numpy as jnp
        from ..core.tensor import nt
        if decode_mod.is_vector_pos(state.pos):
            # per-slot positions: each row divides by its own 1 + pos
            return cumsum(args) / nt(
                jnp.asarray(1 + state.pos, args.tensor.data.dtype),
                [args.params.batch_dim])
        return cumsum(args) / nt(jnp.asarray(1 + state.pos,
                                             args.tensor.data.dtype), ())
    return cumsum(args) / (1 + range_(dim, args.tensor.dtype))


def yarn_inv_freq(theta: float, width: int, factor: float, original: int,
                  beta_fast: float, beta_slow: float):
    """YaRN's rotary frequencies for ``width`` rotated features (Peng et
    al., arXiv:2309.00071; HF ``_compute_yarn_parameters``, ``truncate``
    true): frequency ``i`` is ``theta ** (-2i / width)`` where it turns more
    than ``beta_fast`` times in ``original`` positions, that over ``factor``
    where it turns fewer than ``beta_slow`` times, and a linear blend over
    the feature index in between.  A numpy float32 vector ``[width / 2]``,
    made while tracing."""
    import math
    import numpy as np

    def correction_dim(rotations: float) -> float:
        return width * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), width - 1)
    if low == high:
        high += 0.001
    plain = theta ** (-np.arange(0, width, 2, dtype=np.float64) / width)
    ramp = np.clip((np.arange(width // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    return (plain / factor * ramp + plain * (1 - ramp)).astype(np.float32)


def rotary(x, theta: float, width: typing.Optional[int] = None,
           inv_freq=None, factor: float = 1.0):
    """Rotary position embedding on ``x [..., seq, heads, width]``, HF's
    rotate-half convention over the whole head width: feature ``i`` pairs
    with ``i + width/2``, both turn by ``pos * theta ** (-2i / width)``.
    Computed in float32, returned in ``x``'s dtype.  ``width`` (None = all):
    only the FIRST ``width`` features of each head turn, rotate-half inside
    them, the rest pass (HF's ``partial_rotary_factor``); ``inv_freq``
    ``[width / 2]`` replaces the plain frequencies (``yarn_inv_freq``);
    ``factor`` multiplies cos and sin (YaRN's ``attention_factor``)."""
    import jax.numpy as jnp
    seq, full = x.shape[-3], x.shape[-1]
    width = full if width is None else width
    half = width // 2
    if inv_freq is None:
        inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / width)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq)[None, :]
    cos = jnp.cos(angle)[:, None, :]
    sin = jnp.sin(angle)[:, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:width]
    parts = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    if width != full:
        parts.append(xf[..., width:])
    return jnp.concatenate(parts, axis=-1).astype(x.dtype)


#: the standard attention's flags: the three that select it (how positions
#: enter), the plain ones, and the ones that carry a whole number
_STANDARD_POSITION = ("rope", "nope", "yarn")
_STANDARD_PLAIN = ("qk_norm", "qk_norm_head", "gate", "gate_features",
                   "sparse", "indexed", "block_diffusion")
#: what the block-diffusion mask (``block_diffusion``) does not build, each
#: refused by name
_BLOCK_DIFFUSION_REFUSES = ("sparse", "indexed", "window", "kv_latent")
_STANDARD_NUMBERED = ("q_heads", "kv_heads", "window", "rotary_pct", "theta",
                      "kv_latent", "shared_key", "q_latent")
#: what the latent form (``kv_latent<n>``) does not build, each refused by
#: name
_LATENT_REFUSES = ("yarn", "rotary_pct", "qk_norm", "qk_norm_head", "gate",
                   "gate_features", "sparse", "indexed", "window")


def numbered_flags(extras, plain, numbered, what: str
                   ) -> typing.Dict[str, typing.Any]:
    """A layer's flags as ``{name: True | int}``: the ``plain`` ones, and the
    ``numbered`` ones followed by a whole number; ``what`` (the layer, in
    words) refuses any other by name, with the flags it knows."""
    out: typing.Dict[str, typing.Any] = {}
    for extra in extras:
        if extra in plain:
            out[extra] = True
            continue
        name = extra.rstrip("0123456789")
        if name not in numbered or name == extra:
            raise ValueError(
                f"{what} does not know flag {extra!r} (known: "
                f"{tuple(plain)}, and {tuple(numbered)} followed by a whole "
                "number)")
        out[name] = int(extra[len(name):])
    if ("q_heads" in out) != ("kv_heads" in out):
        raise ValueError("q_heads<n> and kv_heads<n> come together")
    if "q_heads" in out and (out["kv_heads"] < 1
                             or out["q_heads"] % out["kv_heads"]):
        raise ValueError(f"kv_heads{out['kv_heads']} must divide "
                         f"q_heads{out['q_heads']}")
    return out


def _standard_flags(extras) -> typing.Dict[str, typing.Any]:
    """The standard attention's flags as ``{name: True | int}``; a flag it
    does not know, or two ways of placing positions, refuse by name.
    (Compressed convolutional attention is a layer of its own, ``cca``,
    model/cca.py, with its own flags.)"""
    out = numbered_flags(extras, _STANDARD_POSITION + _STANDARD_PLAIN,
                         _STANDARD_NUMBERED, "the standard attention")
    if sum(f in out for f in _STANDARD_POSITION) != 1:
        raise ValueError("the standard attention takes exactly one of "
                         f"{_STANDARD_POSITION}, got {list(extras)}")
    if "nope" in out and any(f in out for f in ("rotary_pct", "theta")):
        raise ValueError("nope (no rotary positions) with rotary_pct / theta")
    for one, other in (("qk_norm", "qk_norm_head"), ("gate", "gate_features"),
                       ("sparse", "window"), ("indexed", "window"),
                       ("sparse", "indexed")):
        if one in out and other in out:
            raise ValueError(f"the standard attention takes {one} or "
                             f"{other}, not both")
    if "block_diffusion" in out:
        for flag in _BLOCK_DIFFUSION_REFUSES:
            if flag in out:
                raise ValueError(
                    f"the block-diffusion mask (block_diffusion) does not "
                    f"build {flag}: every key of the clean half's earlier "
                    "blocks and of the query's own block is seen (no sparse "
                    "or indexed choice, no window), from plain keys and "
                    "values (no kv_latent)")
    for flag in ("shared_key", "q_latent"):
        if flag in out and "kv_latent" not in out:
            raise ValueError(
                f"{flag}<n> (one key part shared by all heads / a query "
                "latent) comes with kv_latent<n>")
    if "kv_latent" in out:
        for flag in _LATENT_REFUSES:
            if flag in out:
                raise ValueError(
                    f"latent attention (kv_latent<n>) does not build {flag}: "
                    "rotary turns the shared key part whole at the plain "
                    "frequencies (rope, theta<t>; no yarn, no rotary_pct), "
                    "and there is no query / key norm beside the latents' "
                    "own, no gate, no sparse or indexed choice, no window")
        if out.get("q_heads") != out.get("kv_heads"):
            raise ValueError("latent attention expands the latent to a key "
                             "and a value a query head: kv_heads = q_heads")
        if min(out["kv_latent"], out.get("shared_key", 1),
               out.get("q_latent", 1)) < 1:
            raise ValueError("kv_latent<n>, shared_key<n> and q_latent<n> "
                             "are positive")
        if "rope" in out and (out.get("shared_key", 0) < 2
                              or out["shared_key"] % 2):
            raise ValueError(
                "latent attention under rope turns the shared key part "
                "(and the last shared_key features of every query head): "
                "shared_key<n> with n even and positive, got "
                f"{out.get('shared_key', 0)}")
    return out


def project(args: BlockArgs, x: NamedTensor, new, old,
            stddev: float = 0.02) -> NamedTensor:
    """A bias-free projection ``old`` features -> ``new``, normal(``stddev``):
    the input's ``old`` dims renamed so that the einsum contracts them."""
    from ..core.tensor import rename_dim
    from .backend import normal_var
    from .utils import anonymize_dim
    hidden = [anonymize_dim(d) for d in old]
    for d, a in zip(old, hidden):
        x = rename_dim(x, d.name, a.name)
    return einsum([x, normal_var(args, hidden + new, stddev=stddev)],
                  shape_sub(x.dims, hidden) + new)


def rotary_width(features: int, pct: typing.Optional[int]) -> int:
    """The first ``pct`` percent of a head's ``features`` (flag
    ``rotary_pct<p>``; None = all): an even count, or it refuses."""
    width, rest = divmod(features * (100 if pct is None else pct), 100)
    if rest or width < 2 or width % 2 or width > features:
        raise ValueError(f"rotary_pct{pct}: an even number of "
                         f"features_per_head {features}'s features")
    return width


def causal_heads(ctx, params, q, k, v, group: int, scale: float,
                 window=None):
    """Causal ``softmax(scale q k^T) v`` on ``q [lead, seq, heads, f]`` and
    ``k``, ``v`` ``[lead, seq, heads / group, f]``: each K/V head repeated
    over its ``group`` of query heads before the kernel, autodiff sums dk
    and dv over it (the flash kernels are multi-head only; PERF.md section
    6, PR 30 has the A/B against K/V index maps); the flash kernel, or
    under ``use_flash_attention`` false XLA's dense form."""
    import jax
    import jax.numpy as jnp
    if group > 1:
        k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
    if params.use_flash_attention:
        return _flash(ctx, q, k, v, scale, window)
    from ..parallel.flash_attention import _xla_reference
    with jax.named_scope("attention_dense"):
        return _xla_reference(q, k, v, scale, True, window)


def _one_device(ctx, flag: str) -> None:
    """A choice of keys is made on one device: ``flag`` refuses a mesh."""
    if ctx.mesh is not None and ctx.mesh.size > 1:
        raise NotImplementedError(f"attention flag {flag} on a mesh: the "
                                  "choice of keys is made on one device")


def block_diffusion_heads(ctx, params, q, k, v, group: int, scale: float):
    """Attention flag ``block_diffusion`` on the stream's halves folded into
    the lead axis, noised before clean a sequence: ``q [2 lead, L, heads, f]``
    and ``k``, ``v`` ``[2 lead, L, heads / group, f]`` -> ``[2 lead, L,
    heads, f]`` under the block-diffusion mask of ``diffusion_block``
    (parallel/flash_attention.py ``block_diffusion_attention``: a query's far
    part over the CLEAN half's keys of earlier blocks — the kernels —, its
    own block in its own half, merged by log-sum-exp).  The CLEAN half's K/V
    heads are repeated over their group for the kernels, as ``causal_heads``
    repeats them, in the copy that hands them to both halves; the own blocks
    read K/V a K/V head each.  ``use_flash_attention`` false, the CPU and
    shapes the kernels do not tile run the same two parts in XLA.  Scopes
    ``halves`` (the clean half's keys and values for both), ``own_block``,
    ``lse_merge``."""
    import jax
    import jax.numpy as jnp
    from ..parallel.flash_attention import block_diffusion_attention
    _one_device(ctx, "block_diffusion")

    def clean(t):
        # ONE copy: the clean half's K/V heads over their group of query
        # heads and over both halves
        lead, (length, heads, width) = t.shape[0] // 2, t.shape[1:]
        pair = t.reshape(lead, 2, length, heads, 1, width)[:, 1:]
        return jnp.broadcast_to(
            pair, (lead, 2, length, heads, group, width)).reshape(
                2 * lead, length, heads * group, width)

    with jax.named_scope("halves"):
        k_clean, v_clean = clean(k), clean(v)
    return block_diffusion_attention(
        q, k, v, k_clean, v_clean, params.diffusion_block, scale,
        stash=stash_channel(ctx, "attention"),
        kernels=params.use_flash_attention)


def sparse_heads(ctx, params, q, k, v, scale: float):
    """Attention flag ``sparse`` on ``q [lead, seq, heads, f]`` and ``k``,
    ``v`` ``[lead, seq, kv heads, f]``: the plain causal attention up to
    ``sparse_dense_length`` keys; past it the indexer's choice (no gradient;
    named ``SELECT_NAME``) and the selected kernels on it.  Reports the kept
    share of the visible keys and the share of queries that chose."""
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name
    from ..parallel.flash_attention import select_attention
    from . import sparse
    sizes = sparse.sizes_of(params)
    if not sparse.selects(sizes, q.shape[1]):
        if ctx.layer_stats is not None:
            ctx.layer_stats.append(
                {"sparse_kept_key_share": jnp.float32(1.0),
                 "sparse_choosing_query_share": jnp.float32(0.0)})
        return causal_heads(ctx, params, q, k, v, q.shape[2] // k.shape[2],
                            scale)
    _one_device(ctx, "sparse")
    with jax.named_scope("sparse_attention"):
        keep = checkpoint_name(sparse.select_blocks(
            jax.lax.stop_gradient(q), jax.lax.stop_gradient(k), sizes,
            scale), SELECT_NAME)
        if ctx.layer_stats is not None:
            share, chose = sparse.kept_shares(keep, sizes.block)
            ctx.layer_stats.append({"sparse_kept_key_share": share,
                                    "sparse_choosing_query_share": chose})
        with jax.named_scope("attend"):
            return select_attention(q, k, v, keep, sizes.block, scale,
                                    stash=stash_channel(ctx, "attention"))


def index_inputs(args: BlockArgs, feats, lead_dims, dim, theta: float):
    """Attention flag ``indexed``'s own parameters, on ``stop_gradient`` of
    the block's input: ``(qI [lead, seq, index_heads, index_features], kI
    [lead, seq, index_features], w [lead, seq, index_heads] float32)`` of
    model/indexer.py — a projection to the index queries, one to the single
    index key under a LayerNorm (learned scale and shift, ``norm_epsilon``),
    one to a weight a head times ``index_heads ** -0.5``; rotary at ``theta``
    over all the index features of queries and key."""
    import jax
    import jax.numpy as jnp
    from ..core.tensor import nt, transpose_to
    from .normalization import norm
    params = args.params
    heads = Dim("index_heads", params.index_heads)
    width = Dim("index_features", params.index_features)
    x = nt(jax.lax.stop_gradient(args.tensor.data), args.tensor.dims)
    qry = project(args, x, [heads, width], feats)
    key = norm(args(project(args, x, [width], feats), ["scale", "shift"]),
               [width])
    weight = project(args, x, [heads], feats)
    lead = 1
    for d in lead_dims:
        lead *= d.size

    def flat(t, tail):
        return transpose_to(t, lead_dims + [dim] + tail).data.reshape(
            lead, dim.size, *(d.size for d in tail))

    with jax.named_scope("rope"):
        q_index = rotary(flat(qry, [heads, width]), theta)
        k_index = rotary(flat(key, [width])[:, :, None], theta)[:, :, 0]
    return q_index, k_index, flat(weight, [heads]).astype(jnp.float32) \
        * params.index_heads ** -0.5


def indexed_heads(ctx, params, q, k, v, scale: float, index):
    """Attention flag ``indexed`` on ``q [lead, seq, heads, f]`` and ``k``,
    ``v`` ``[lead, seq, kv heads, f]`` with ``index_inputs``' three: the
    plain causal attention up to ``index_topk`` keys; past it the indexer's
    choice of single keys, one for all heads (no gradient; named
    ``SELECT_NAME``), and the selected kernels on it.  Either way the
    indexer trains on its own loss (model/indexer.py ``index_loss`` /
    ``inject``).  Reports the kept share of the visible keys, the share of
    queries that chose, the index loss and the largest kept |score|."""
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name
    from ..parallel.flash_attention import key_select_attention
    from . import indexer
    _one_device(ctx, "indexed")
    detached = tuple(jax.lax.stop_gradient(x) for x in index)
    stats = ctx.layer_stats is not None
    keep = lse = None
    with jax.named_scope("sparse_attention"):
        if indexer.selects(params.index_topk, q.shape[1]):
            keep = checkpoint_name(indexer.select_keys(
                *detached, params.index_topk), SELECT_NAME)
            with jax.named_scope("attend"):
                out, lse = key_select_attention(
                    q, k, v, keep, scale,
                    stash=stash_channel(ctx, "attention"))
    if keep is None:
        out = causal_heads(ctx, params, q, k, v, q.shape[2] // k.shape[2],
                           scale)
    if not (params.train or stats):
        return out
    with jax.named_scope("sparse_attention"):
        value, top, *grads = indexer.named_index_loss(
            *detached, q, k, lse, keep, scale=scale)
        if params.train:
            out = indexer.inject(out, *index, *grads)
        if stats:
            share, chose = (jnp.float32(1.0), jnp.float32(0.0)) \
                if keep is None else indexer.kept_shares(keep)
            ctx.layer_stats.append({
                "sparse_kept_key_share": share,
                "sparse_choosing_query_share": chose,
                "index_loss": value, "index_score_abs_max": top})
    return out


def _standard_attention(args: BlockArgs) -> NamedTensor:
    """The standard pre-norm transformer's attention (flag ``rope``, ``nope``
    or ``yarn``): query, key and value are three bias-free projections of the
    block's input (no bottleneck) — the query to all ``heads``, key and value
    to ``heads // query_group`` of them (``query_group`` 1 = all; more =
    grouped queries, K/V head ``j`` serves query heads ``j * query_group
    ..``: K and V are repeated over the group for the kernel) — with
    ``qk_norm`` an RMSNorm with a learned scale over ALL heads' features of
    the query and of the key before the head split (OLMoE), rotary positions
    on both under
    ``rope`` (``rope_theta``) and none under ``nope`` (Granite 4.0-H: the
    Mamba layers carry the order), causal ``softmax(scale q k^T) v`` with
    ``scale = attention_scale`` (0 = ``features_per_head ** -0.5``) through
    the flash kernel, and an output projection.  Weights are normal(0.02),
    the output projection's normal(``residual_out_stddev``) where that is set.

    A layer's own head counts: ``q_heads<n>-kv_heads<m>`` project the query
    to ``n`` heads and key and value to ``m`` of ``features_per_head`` each,
    whatever the stream's ``heads`` (the stream is ``heads x
    features_per_head`` wide, the attention ``n x features_per_head``; the
    output projection leads back); ``query_group`` is then ``n / m`` for
    this layer.  ``window<w>``: query ``i`` sees keys ``i - w + 1 .. i``
    (the flash kernels skip the blocks behind the window).  ``gate``: a
    sigmoid gate a query head on the attention's output, ``o * sigmoid(a
    Wg)`` with ``Wg [features, heads]`` from the block's (normed) input,
    created after the value projection (Qiu et al., arXiv:2505.06708,
    head-wise); ``gate_features``: a gate a FEATURE, ``Wg [features, heads x
    features_per_head]`` (MiniCPM-SALA's ``attn_use_output_gate``).
    ``qk_norm_head``: the RMSNorm over each head's own features, one learned
    ``[features_per_head]`` scale for the query's heads and one for the
    key's.  ``sparse``: past ``sparse_dense_length`` keys a query attends the
    blocks of keys the indexer of model/sparse.py keeps for its K/V group
    (steps ``compress``, ``index``, ``select`` under scope
    ``sparse_attention``, then ``attend``: the ``flash_*_select`` kernels of
    parallel/flash_attention.py); the choice carries no gradient and is
    named (``SELECT_NAME``) beside ``(out, lse)``, so that where those are
    saved a replay chooses nothing.  ``indexed``: past ``index_topk`` keys a
    query attends the ``index_topk`` single keys that a learned indexer of
    its own parameters (``index_inputs``: ``index_heads`` index queries of
    ``index_features``, one index key, a weight a head, all from
    ``stop_gradient`` of the block's input; model/indexer.py) scores highest,
    ONE choice for all the layer's heads (steps ``index``, ``select``,
    ``attend``, ``index_loss`` under scope ``sparse_attention``; the
    ``flash_*_select`` kernels' key-at-a-time form); the choice carries no
    gradient and is named like ``sparse``'s; the indexer learns from a KL
    loss to the attention's own head-mean probabilities, whose gradient
    reaches only it.  ``theta<t>`` replaces ``rope_theta`` for this layer,
    ``rotary_pct<p>`` turns only the first ``p`` percent of each head's
    features (HF's ``partial_rotary_factor``; an even count), and
    ``yarn`` is ``rope`` at YaRN's frequencies (``rope_yarn_factor``,
    ``rope_yarn_original_positions``, ``rope_yarn_beta_fast`` /
    ``_beta_slow``) with cos and sin times ``rope_yarn_attention_factor``
    (0 = ``0.1 ln(factor) + 1``).  ``block_diffusion``: the layer mixes the
    doubled stream of block-diffusion training (``diffusion_block`` > 0,
    model/denoise.py: ``[noised | clean]``, ``2 L`` positions) under its mask
    — a noised query sees the noised keys of its own block of
    ``diffusion_block`` (both directions) and the clean keys of earlier
    blocks, a clean query the clean keys of its own and earlier blocks —
    with rotary by ``index mod L`` (each half turned by its own index);
    ``block_diffusion_heads``: the far part through the
    ``flash_*_blockdiff`` kernels, scopes ``halves``, ``own_block``,
    ``lse_merge``; no sparse or indexed choice, window or latent form with
    it.  Any other flag refuses by name
    (``_standard_flags``, whose message lists the ones it knows).
    Compressed convolutional attention is NOT a flag of this function: it is
    layer ``cca`` (model/cca.py), which shares ``project``, ``rotary``,
    ``rotary_width`` and ``causal_heads`` with it.
    Training and full-sequence forward only: a decode step for it is a later
    issue."""
    import jax
    import jax.numpy as jnp
    from ..core import scope as scope_mod
    from ..core.tensor import nt, transpose_to
    from .normalization import norm
    params = args.params
    flags = _standard_flags(args.name_extras)
    ctx = scope_mod.current()
    dim = get_attention_dim(args).dim
    if ctx.decode is not None or decode_mod.prefill_active() is not None:
        raise NotImplementedError(
            "the standard attention (attention-rope / attention-nope, "
            "grouped heads or not) has no incremental decode / prefill form "
            "yet")
    mesh = ctx.mesh
    if mesh is not None and (mesh.shape.get(shardlib.SEQUENCE_AXIS, 1) > 1
                             or mesh.shape.get(shardlib.PIPE_AXIS, 1) > 1):
        raise NotImplementedError(
            "the standard attention (attention-rope / attention-nope) on a "
            "sequence- or pipe-sharded mesh")
    if "kv_latent" in flags:
        if mesh is not None and mesh.size > 1:
            raise NotImplementedError("latent attention (kv_latent<n>) on a "
                                      "mesh")
        return _latent_attention(args, flags)
    feats = list(params.feature_dims)
    own_heads = "q_heads" in flags
    if own_heads:
        group = flags["q_heads"] // flags["kv_heads"]
        kv_heads = flags["kv_heads"]
        q_feats = [Dim("q_heads", flags["q_heads"]), params.key_dim]
    else:
        group = params.query_group
        kv_heads = params.head_dim.size // group
        q_feats = feats
    grouped = group > 1
    kv_feats = [Dim("kv_heads", kv_heads), params.key_dim] \
        if grouped or own_heads else feats
    if (grouped or own_heads) and mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "grouped key / value heads, or a layer's own head counts, on a "
            "mesh")

    # creation order: key, query, value (as the dense path), the gate, the
    # two norms, the output projection
    key = project(args, args.tensor, kv_feats, feats)
    qry = project(args, args.tensor, q_feats, feats)
    val = project(args, args.tensor, kv_feats, feats)
    gate = None
    if "gate" in flags or "gate_features" in flags:
        with jax.named_scope("gate"):
            gate = project(args, args.tensor,
                           q_feats if "gate_features" in flags
                           else q_feats[:1], feats)
    if "qk_norm" in flags:
        qry = norm(args(qry, ["rms", "scale"]), q_feats)
        key = norm(args(key, ["rms", "scale"]), kv_feats)
    if "qk_norm_head" in flags:
        qry = norm(args(qry, ["rms", "scale"]), q_feats[1:])
        key = norm(args(key, ["rms", "scale"]), kv_feats[1:])
    lead_dims = [d for d in args.tensor.dims if d not in [dim] + feats]
    if "indexed" in flags:
        with jax.named_scope("sparse_attention"), jax.named_scope("index"):
            index = index_inputs(args, feats, lead_dims, dim, float(
                flags.get("theta", params.rope_theta)))
    canonical = lead_dims + [dim] + q_feats
    lead = 1
    for d in lead_dims:
        lead *= d.size
    q = transpose_to(qry, canonical).data.reshape(
        lead, dim.size, q_feats[0].size, params.key_dim.size)
    k, v = (transpose_to(t, lead_dims + [dim] + kv_feats).data.reshape(
        lead, dim.size, kv_heads, params.key_dim.size) for t in (key, val))
    if "block_diffusion" in flags:
        if not params.diffusion_block or dim.size != params.sequence_dim.size:
            raise ValueError(
                "attention flag block_diffusion mixes the doubled stream of "
                "block-diffusion training (diffusion_block > 0, "
                f"model/denoise.py) along the sequence; got diffusion_block "
                f"{params.diffusion_block} and axis {dim}")
        # the halves fold into the lead axis, noised before clean: rotary
        # then turns each by its own index, position = index mod L
        q, k, v = (t.reshape((2 * lead, dim.size // 2) + t.shape[2:])
                   for t in (q, k, v))
    if "nope" not in flags:
        theta = float(flags.get("theta", params.rope_theta))
        rope_args: typing.Tuple = ()
        if "rotary_pct" in flags or "yarn" in flags:
            width = rotary_width(params.key_dim.size,
                                 flags.get("rotary_pct"))
            rope_args = (width,)
            if "yarn" in flags:
                import math
                factor = params.rope_yarn_attention_factor \
                    or 0.1 * math.log(params.rope_yarn_factor) + 1.0
                rope_args = (width, yarn_inv_freq(
                    theta, width, params.rope_yarn_factor,
                    params.rope_yarn_original_positions,
                    params.rope_yarn_beta_fast, params.rope_yarn_beta_slow),
                    float(factor))
        with jax.named_scope("rope"):
            q = rotary(q, theta, *rope_args)
            k = rotary(k, theta, *rope_args)
    scale = params.attention_scale or params.key_dim.size ** -0.5
    if "sparse" in flags:
        out = sparse_heads(ctx, params, q, k, v, scale)
    elif "indexed" in flags:
        out = indexed_heads(ctx, params, q, k, v, scale, index)
    elif "block_diffusion" in flags:
        out = block_diffusion_heads(ctx, params, q, k, v, group, scale)
    else:
        out = causal_heads(ctx, params, q, k, v, group, scale,
                           flags.get("window"))
    out_nt = nt(out.reshape([d.size for d in canonical]), canonical)
    if gate is not None:
        with jax.named_scope("gate"):
            out_nt = out_nt * nt(jax.nn.sigmoid(
                gate.data.astype(jnp.float32)).astype(out.dtype), gate.dims)
    return project(args, transpose_to(
        out_nt, [d for d in args.tensor.dims if d not in feats] + q_feats),
        feats, q_feats, stddev=args.params.residual_out_stddev or 0.02)


def _latent_attention(args: BlockArgs, flags) -> NamedTensor:
    """Latent attention (DeepSeek-V2 / -V3's MLA, arXiv:2412.19437 section
    2.1.1; flags ``kv_latent<c>-shared_key<r>`` with ``q_heads<n>-kv_heads<n>``
    or the stream's heads, ``nope`` or ``rope``, and ``q_latent<cq>`` or
    none): on the block's input ``u``, ``n`` heads of ``d =
    features_per_head``,

        c_q = rms(u W_qa) w_q         features x cq, RMSNorm over the latent
        q = c_q W_qb                  cq x n x (d + r)       (``q_latent<cq>``)
        q = u W_q                     features x n x (d + r)  (without it)
                                      a head's q = [q_n (d) | q_s (r)]
        c | k_s = u W_kvd             features x (c + r); k_s is ONE shared
                                      r-wide key part for all heads
        k_n | v = rms(c) w_c W_kvu    c x n x (d + d); RMSNorm over the
                                      latent, eps ``norm_epsilon``
        q_s, k_s = rotary(q_s), rotary(k_s)    under ``rope`` only: all r
                                      features, pairs (i, i + r/2), theta
                                      ``theta<t>`` or ``rope_theta``,
                                      position = index; ``nope`` turns nothing
                                      (Kimi Linear's ``mla_use_nope``)
        k = [k_n | k_s]               k_s repeated over the heads
        o = causal softmax(scale q k^T) v      scale = attention_scale, or
                                               (d + r)^-1/2
        out = o W_o                   n x d x features

    The flash kernels take the key at ``d + r`` and the value at ``d``
    (parallel/flash_attention.py: V is not padded).  Parameters in creation
    order: (``W_qa``, the query latent's scale, ``W_qb``) or ``W_q``,
    ``W_kvd``, the latent norm's scale, ``W_kvu``, ``W_o``; normal(0.02),
    ``W_o`` normal(``residual_out_stddev``) where set.  Scopes: ``q_down``,
    ``q_norm`` (the query latent's), ``q_proj``, ``kv_down``, ``kv_norm``,
    ``kv_up``, ``latent_rope``, ``attend``, ``out_proj`` (analysis/cost_ledger.py
    folds ``attend`` into ``body/attention`` itself, the mixing the readers
    of that scope mean).  Without ``rope`` and ``q_latent`` the function
    traces what it traced before it knew them.  Not built (refused by name,
    ``_LATENT_REFUSES``): YaRN or a partial turn of the shared part, a norm
    on the queries or keys beside the latents' own, a gate, a sparse or
    indexed choice, a window; decode and prefill (the latent cache and the
    absorbed form are serving's) and a mesh."""
    import jax.numpy as jnp
    from ..core import scope as scope_mod
    from ..core.tensor import nt, transpose_to
    from .normalization import norm
    params = args.params
    ctx = scope_mod.current()
    dim = get_attention_dim(args).dim
    feats = list(params.feature_dims)
    heads = flags.get("q_heads", params.head_dim.size)
    d, r, c = params.key_dim.size, flags.get("shared_key", 0), \
        flags["kv_latent"]
    head, latent = Dim("q_heads", heads), Dim("kv_latent", c)
    lead_dims = [x for x in args.tensor.dims if x not in [dim] + feats]
    tokens = lead_dims + [dim]

    def projected(x, new, old):
        """``x`` projected ``old -> new`` as ``[lead, sequence, *new]``."""
        return transpose_to(project(args, x, new, old), tokens + new
                            ).data.reshape(-1, dim.size,
                                           *(n.size for n in new))

    q_in, q_old = args.tensor, feats
    if "q_latent" in flags:
        q_old = [Dim("q_latent", flags["q_latent"])]
        with jax.named_scope("q_down"):
            q_in = nt(projected(args.tensor, q_old, feats), tokens + q_old)
        with jax.named_scope("q_norm"):
            q_in = norm(args(q_in, ["rms", "scale"]), q_old)
    with jax.named_scope("q_proj"):
        q = projected(q_in, [head, Dim("latent_key", d + r)], q_old)
    with jax.named_scope("kv_down"):
        down = projected(args.tensor, [Dim("kv_latent_shared", c + r)], feats)
    with jax.named_scope("kv_norm"):
        normed = norm(args(nt(down[..., :c], tokens + [latent]),
                           ["rms", "scale"]), [latent])
    shared = None
    if "rope" in flags:
        theta = float(flags.get("theta", params.rope_theta))
        with jax.named_scope("latent_rope"):
            q = jnp.concatenate([q[..., :d], rotary(q[..., d:], theta)],
                                axis=-1)
            shared = rotary(down[:, :, None, c:], theta)
    with jax.named_scope("kv_up"):
        up = projected(normed, [head, Dim("latent_key_value", 2 * d)],
                       [latent])
        k, v = up[..., :d], up[..., d:]
        if r:
            if shared is None:
                shared = down[:, :, None, c:]
            k = jnp.concatenate([k, jnp.broadcast_to(
                shared, k.shape[:3] + (r,))], axis=-1)
    scale = params.attention_scale or (d + r) ** -0.5
    with jax.named_scope("attend"):
        out = causal_heads(ctx, params, q, k, v, 1, scale)
    out_feats = [head, params.key_dim]
    out_nt = nt(out.reshape([x.size for x in tokens + out_feats]),
                tokens + out_feats)
    with jax.named_scope("out_proj"):
        return project(args, transpose_to(
            out_nt, [x for x in args.tensor.dims if x not in feats]
            + out_feats), feats, out_feats,
            stddev=params.residual_out_stddev or 0.02)


def flash_offer(params, heads: int, window: typing.Optional[int] = None
                ) -> Offer:
    """What one flash call over ``heads`` query heads offers the attention
    kind: ``out`` ``[batch, sequence, heads, features_per_head]`` in the
    calculation dtype — the VALUE's width, which under the latent form is
    not the key's — and ``lse`` ``[batch x heads, sequence]`` float32, and
    the keys a query sees."""
    seq = params.sequence_dim.size
    return Offer("attention", SAVED_NAMES,
                 heads * params.batch_dim.size * seq
                 * (params.key_dim.size
                    * np.dtype(params.calculation_dtype).itemsize + 4),
                 keys=min(seq, window or seq))


def _offer(params, extras) -> typing.Optional[Offer]:
    """The call this layer makes through ``_flash`` under training — the
    standard attention its own head count (``q_heads<n>``, else the
    stream's) and its ``window<w>``, the generic one the stream's heads where
    it has a key and no flag that keeps the dense map — or None where it
    makes none."""
    if any(f in extras for f in _STANDARD_POSITION):
        flags = _standard_flags(extras)
        heads = flags.get("q_heads", params.head_dim.size)
        offer = flash_offer(params, heads, flags.get("window"))
        seq = params.sequence_dim.size
        if "shared_key" in flags:
            # the latent form: the shared part widens the key alone
            offer = offer._replace(
                key_width=params.key_dim.size + flags["shared_key"])
        if "block_diffusion" in flags:
            # the far part's pair over both halves; a query sees at most the
            # clean half
            offer = offer._replace(keys=seq // 2,
                                   block=params.diffusion_block)
        if "sparse" in flags and seq > params.sparse_dense_length:
            # the choice rides with the pair: a bool a query, a block and a
            # K/V head
            kv = flags.get("kv_heads", heads // params.query_group)
            offer = offer._replace(
                names=SAVED_NAMES + (SELECT_NAME,),
                nbytes=offer.nbytes + kv * params.batch_dim.size * seq
                * (seq // params.sparse_block_size))
        if "indexed" in flags:
            # the index loss's value and three gradients; past index_topk
            # keys the choice too: a bit a query and a key
            from .indexer import INDEX_LOSS_NAMES
            rows = params.batch_dim.size * seq
            offer = offer._replace(
                names=offer.names + INDEX_LOSS_NAMES,
                nbytes=offer.nbytes + 4 * (1 + rows * (
                    params.index_heads * (params.index_features + 1)
                    + params.index_features)))
            if seq > params.index_topk:
                offer = offer._replace(names=offer.names + (SELECT_NAME,),
                                       nbytes=offer.nbytes + rows * seq // 8)
        return offer
    if "dot_product" not in extras or any(f in extras for f in _DENSE_ONLY) \
            or not any(f in extras for f in ("embedded", "context",
                                              "positional")):
        return None
    return flash_offer(params, params.head_dim.size)


def _reaches_flash_kernels(params, backend=None) -> bool:
    """Whether ``attention``'s dispatch hands this step's flash calls to the
    Pallas kernels: ``use_flash_attention``, off the CPU, a sequence of whole
    128-tiles."""
    if backend is None:
        backend = jax.default_backend()
    return backend != "cpu" and params.use_flash_attention \
        and params.sequence_dim.size % 128 == 0


def flash_band_layers(params, backend=None) -> typing.Optional[int]:
    """How many attention layers of the step run their windowed flash
    FORWARD as the band kernel (``parallel/flash_attention.py _fwd_band``):
    of the layers that offer a flash call with a window shorter than the
    sequence, those the predicate ``attention`` itself calls admits, where
    that call reaches the kernels at all (``use_flash_attention``, off the
    CPU, a sequence of whole 128-tiles); None where no layer declares such a
    window."""
    seq = params.sequence_dim.size
    windows = []
    for offer, times in step_offers(params, "attention"):
        if offer.keys < seq and not offer.block:
            windows += [offer.keys] * times
    if not windows:
        return None
    if not _reaches_flash_kernels(params, backend):
        return 0
    itemsize = np.dtype(params.calculation_dtype).itemsize
    return sum(band_applies(seq, params.key_dim.size, window, itemsize)
               for window in windows)


def flash_scored_over_live(params, backend=None
                           ) -> typing.Optional[typing.Dict[str, float]]:
    """``{"fwd": .., "bwd": ..}``: the pairs the step's tiled causal flash
    kernels score over the pairs its calls have to (``parallel/
    flash_attention.py scored_over_live``: an edge cell is scored as its live
    part, and what of it is still dead shows here), the WORST layer of each
    pass — a windowed layer's backward counts, its band forward, which has no
    such cells, does not.  None where no call reaches those kernels: no layer
    offers a flash call, the CPU, ``use_flash_attention`` off, a sequence of
    no whole 128-tiles, a sparse layer past its dense length (the
    ``flash_*_select`` kernels have their own tables)."""
    if not _reaches_flash_kernels(params, backend):
        return None
    itemsize = np.dtype(params.calculation_dtype).itemsize
    worst: typing.Dict[str, float] = {}
    for offer, _ in step_offers(params, "attention"):
        if SELECT_NAME in offer.names:
            continue
        if offer.block:
            # the block-diffusion mask: ``offer.keys`` the trained tokens
            if not stepped_applies(offer.keys, params.key_dim.size,
                                   offer.block, itemsize):
                continue
            shares = block_diffusion_scored_over_live(
                offer.keys, params.key_dim.size, offer.block, itemsize)
        else:
            # ``offer.keys``: the window, or the sequence where there is none
            shares = scored_over_live(
                params.sequence_dim.size, params.key_dim.size, offer.keys,
                itemsize)
        for name, share in shares.items():
            if share is not None:
                worst[name] = max(worst.get(name, 0.0), share)
    return worst or None


def flash_backward_one_pass_layers(params, backend=None
                                   ) -> typing.Optional[int]:
    """How many attention layers of the step run their flash BACKWARD as the
    one-pass kernel (``parallel/flash_attention.py _bwd_flat_one_pass``: dq,
    dk and dv from one sweep, nothing partial in HBM; a head's dk and dv
    resident, or its dq), by the predicate ``_bwd_flat`` itself calls on the
    call's keys, widths and tiles (``backward_form``) — the other layers
    that reach the causal, windowed or block-diffusion kernels are on the
    split dq / dk-dv pair.  None (the gauge reads 0, the line
    says nothing) where no call reaches them — ``use_flash_attention`` off,
    the CPU, a sequence of no whole 128-tiles — and where no layer offers
    such a call (a sparse or indexed layer past its dense length has the
    ``flash_*_select`` kernels' own backward)."""
    offers = [(offer, times)
              for offer, times in step_offers(params, "attention")
              if SELECT_NAME not in offer.names]
    if not offers or not _reaches_flash_kernels(params, backend):
        return None
    seq, d_v = params.sequence_dim.size, params.key_dim.size
    itemsize = np.dtype(params.calculation_dtype).itemsize
    layers = 0
    for offer, times in offers:
        d_k = offer.key_width or d_v
        keys, window = seq, offer.keys if offer.keys < seq else None
        if offer.block:
            # the far part: the causal grid over the trained tokens
            keys, window = offer.keys, None
            if not stepped_applies(keys, d_k, offer.block, itemsize, d_v):
                continue
        blk = call_tiles(keys, d_k, window, itemsize, d_v)[0]
        layers += times * (backward_form(keys, keys, d_k, d_v, blk, blk,
                                         itemsize, window=window) != "split")
    return layers


def index_loss_kernel_layers(params, backend=None) -> typing.Optional[int]:
    """How many attention layers of the step (flag ``indexed``) run their
    index loss as the kernel of ``parallel/index_loss.py``, by the predicate
    ``model/indexer.py index_loss`` itself calls: a TPU, a sequence past
    ``index_topk`` (the choice as bits and the ``lse`` over it) of whole
    tiles; None where no layer has the flag."""
    from ..parallel.index_loss import kernel_applies
    from .indexer import INDEX_LOSS_NAMES
    layers = sum(times for offer, times in step_offers(params, "attention")
                 if INDEX_LOSS_NAMES[0] in offer.names)
    if not layers:
        return None
    seq = params.sequence_dim.size
    return layers * kernel_applies(seq, seq > params.index_topk, backend)


def index_loss_walked_over_visible(params, backend=None
                                   ) -> typing.Optional[float]:
    """The (query, key) pairs an ``indexed`` layer's index loss walks over
    the ``s (s + 1) / 2`` a query may see (``model/indexer.py
    walked_over_visible``: the kernel's tiles under the diagonal or the XLA
    form's bands); None where no layer has the flag."""
    from .indexer import walked_over_visible
    layers = index_loss_kernel_layers(params, backend)
    if layers is None:
        return None
    return walked_over_visible(params.sequence_dim.size, layers > 0)


#: layer ``attention``'s ``declares.facts``
FACTS = (
    Fact(60, "hbnlp_flash_band_layers",
         "attention layers of the built step whose windowed flash forward is "
         "the band kernel (0 on the tiled forward, on the CPU and without a "
         "windowed layer)",
         lambda params, mesh, backend: flash_band_layers(params, backend),
         "flash band {} layers"),
    Fact(61, "hbnlp_flash_scored_over_live_pairs",
         "pairs the step's tiled causal flash kernels score over the pairs "
         "its calls have to, the worst layer of each pass (1.0: only the "
         "band; no series where no call reaches those kernels)",
         lambda params, mesh, backend: flash_scored_over_live(params, backend),
         "flash scored over live pairs {}", zero=False, label="pass"),
    Fact(62, "hbnlp_index_loss_kernel_layers",
         "attention layers of the built step (flag indexed) whose index loss "
         "is the Pallas kernel (0 off the TPU, at or under index_topk keys "
         "and at a sequence of no whole tiles; no series without the flag)",
         lambda params, mesh, backend: index_loss_kernel_layers(params,
                                                                backend),
         "index loss kernel {} layers", zero=False),
    Fact(63, "hbnlp_index_loss_walked_over_visible_pairs",
         "pairs an indexed layer's index loss walks over the pairs a query "
         "may see (the kernel: its tiles at or under the diagonal; the XLA "
         "form: its bands of chunks; no series without the flag)",
         lambda params, mesh, backend: index_loss_walked_over_visible(
             params, backend),
         "index loss walked over visible pairs {:.6g}", zero=False),
    Fact(64, "hbnlp_flash_backward_one_pass_layers",
         "attention layers of the built step whose flash backward is the "
         "one-pass kernel (the other layers that reach the flash kernels are "
         "on the split dq / dk-dv pair; 0 on the CPU, where no call reaches "
         "the kernels and without such a layer)",
         lambda params, mesh, backend: flash_backward_one_pass_layers(
             params, backend),
         "flash backward one pass {} layers"),
)


def attention(args: BlockArgs) -> NamedTensor:
    params = args.params
    params.attention_idx += 1
    if any(f in args.name_extras for f in _STANDARD_POSITION):
        return _standard_attention(args)
    base = None
    if "dot_product" in args.name_extras or "input_as_value" not in args.name_extras:
        base = args(activated_linear_in(args))

    dim = get_attention_dim(args).dim
    tmp = _key_dim(dim)
    shape = list(args.tensor.dims)

    logit: typing.Union[NamedTensor, int] = 0
    val: typing.Union[NamedTensor, int] = 0
    key: typing.Union[NamedTensor, int] = 0
    if "dot_product" in args.name_extras:
        if "embedded" in args.name_extras or "context" in args.name_extras:
            key = activated_linear_out(base)
        if "embedded" in args.name_extras or "positional" in args.name_extras:
            key = key + embed(args, [dim] + list(params.feature_dims)) if \
                isinstance(key, NamedTensor) else embed(args, [dim] + list(params.feature_dims))
        qry = activated_linear_out(base)
        qry = qry * tmp.size ** -0.5  # full length also under decode (dim is the length-1 slice)
        ring_out = _maybe_ring_attention(args, dim, qry, key, base)
        if ring_out is not None:
            return ring_out
        flash_out = _maybe_flash_attention(args, dim, qry, key, base)
        if flash_out is not None:
            return flash_out
        logit_shape = shape_sub(shape, shape_sub(linear_shapes(args).old,
                                                 [params.head_dim])) + [tmp]
        logit = einsum([qry, _anonymize_kv(key, dim)], output_shape=logit_shape)
        if "shared_key_value" in args.name_extras:
            val = key
    if "biased_softmax" in args.name_extras:
        logit = logit + multiply(*_masked_map(args))
    if isinstance(logit, NamedTensor):
        logit = logit + (compare_range(params, dim, tmp, less) * 1e38) * -2
        logit = logit - stop_gradient(reduce_max(logit, reduced_dim=tmp))
        logit = exp(logit)
        logit = logit / reduce_sum(logit, reduced_dim=tmp)
    if "biased_attention_map" in args.name_extras:
        bias, mask = _masked_map(args)
        if not isinstance(logit, NamedTensor) and not isinstance(val, NamedTensor):
            mixed = _maybe_map_mixer(args, dim, bias, mask, base)
            if mixed is not None:
                return mixed
        logit = logit + multiply(bias, mask)
    if "scale_attention_map" in args.name_extras:
        logit = logit * multiply(*_masked_map(args))
    if not isinstance(val, NamedTensor):
        val = _anonymize_kv(args.tensor if "input_as_value" in args.name_extras
                            else activated_linear_out(base), dim)
    if not isinstance(logit, NamedTensor):
        raise UserWarning(f"no spatial mixing with attention parameters: {args.name_extras}")
    return einsum([logit, val], shape)


attention.declares = Layer(
    stats=(Stat("sparse_kept_key_share", "gauge",
                "hbnlp_sparse_kept_key_share",
                "keys a query of a sparse attention layer kept over the keys "
                "it may see, mean over the queries of the newest finished "
                "step, in the layer where it is smallest (1 = dense)",
                "min"),
           Stat("sparse_choosing_query_share", "gauge",
                "hbnlp_sparse_choosing_query_share",
                "share of a sparse attention layer's queries that left a "
                "visible block out, newest finished step, the layer where it "
                "is largest", "max"),
           Stat("index_loss", "gauge", "hbnlp_index_loss",
                "attention flag indexed: the indexer's KL loss to the "
                "attention's head-mean probabilities over the kept keys, "
                "nat, mean over the layers of the newest finished step",
                lambda stats, done: stats["index_loss"].mean()),
           Stat("index_score_abs_max", "gauge", "hbnlp_index_score_abs_max",
                "attention flag indexed: the largest |index score| among the "
                "kept (query, key) pairs, newest finished step, the layer "
                "where it is largest", "max")),
    offer=_offer, facts=FACTS)
