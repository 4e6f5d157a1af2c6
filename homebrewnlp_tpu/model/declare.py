"""What a layer declares of itself, as ONE object set on its function beside
its definition (``<layer function>.declares = Layer(...)``): the statistics it
reports, what it offers a memory strategy to keep, and the start-up facts of
the mechanisms it runs.  ``train/__init__.py`` and ``model/remat.py`` read
declarations and name no layer: a new layer or kernel is its own file and a
``LAYER_FUNCTIONS`` row.
"""
from __future__ import annotations

import typing

import jax.numpy as jnp

from ..config import ModelParameter

#: ``"each"``: no fold, the layers' (a looped model's passes') values as they
#: are — a statistic with a ``label``
_FOLDS = {"max": jnp.max, "min": jnp.min, "sum": jnp.sum, "mean": jnp.mean,
          "each": lambda values: values}


class Stat(typing.NamedTuple):
    """One step metric made of what a layer appends to ``ctx.layer_stats``
    under ``key`` (``name`` where empty; ``Model.apply`` merges the layers'
    scalars into one array a key).  ``fold``: ``"max"`` / ``"min"`` /
    ``"sum"`` over the layers (``"each"``: none), or a function of ``(the merged dict, this
    layer's metrics folded before it)``.  ``Trainer._publish_layer_stats``
    publishes it as the ``kind`` (``"gauge"`` / ``"counter"``) ``metric``.
    With a ``label`` the fold gives a vector, and the step reports one metric
    ``<name>/<index>`` an entry, published under ``metric{label="<index>"}``
    (a looped model's statistics a pass, model/loop.py)."""
    name: str
    kind: str
    metric: str
    help: str
    fold: typing.Union[str, typing.Callable[[dict, dict], typing.Any]]
    key: str = ""
    label: str = ""


class Offer(typing.NamedTuple):
    """What one layer of the configuration offers a memory strategy to keep
    for its backward: the stash ``kind`` (``model/remat.py STASH_KINDS``),
    the ``checkpoint_name``s it tags them with (none for a kind that rides
    the revnet / momentum channel alone), their bytes for the whole batch
    over its ``count`` outputs and, for a flash call, the ``keys`` a query
    sees (``min(sequence, window)``).  ``interior_names`` /
    ``interior_nbytes``: what, kept WITH ``names``, lets the replay skip the
    layer's own forward kernels (the residuals of a rule run as Pallas
    pairs) — a second part, admitted on top of the first and never without
    it.  ``block``: a flash call under the block-diffusion mask
    (``diffusion_block``; 0 = causal), whose ``keys`` are the trained tokens
    a sequence.  ``key_width``: a flash call's key width where it is not the
    value's (the latent form's ``d + r``; 0 = the value's)."""
    kind: str
    names: typing.Tuple[str, ...]
    nbytes: int
    count: int = 1
    keys: typing.Optional[int] = None
    interior_names: typing.Tuple[str, ...] = ()
    interior_nbytes: int = 0
    block: int = 0
    key_width: int = 0


class Fact(typing.NamedTuple):
    """One start-up gauge of the step a trainer builds, declared by the
    module that owns the mechanism: ``value(params, mesh, backend)`` — through
    the predicate the layer itself calls; None where no layer of the
    configuration has the mechanism — ``fragment`` — its part of the ``remat
    stash:`` line, a format of the value, printed where the value is not None
    — ``place`` — where on that line — ``zero`` — whether the gauge reads
    0 where the value is None (else it has no series) — and ``label``: where
    set, the gauge has that one label and the value is ``{label value:
    number}``, printed as ``<label value> <number>`` pairs."""
    place: int
    metric: str
    help: str
    value: typing.Callable[[ModelParameter, typing.Any, typing.Optional[str]],
                           typing.Union[None, int, typing.Dict[str, float]]]
    fragment: str
    zero: bool = True
    label: str = ""


class Layer(typing.NamedTuple):
    """``offer(params, extras)`` returns the layer's :class:`Offer` under
    these flags, or None; ``recurrent`` is a recurrent mixer's
    ``model/recurrent.py Recurrent``; ``carried(params)`` the bytes of the
    carried side values (``Context.side``, model/blocks.py) that the step's
    layers of this kind keep alive between blocks for the backward."""
    stats: typing.Tuple[Stat, ...] = ()
    offer: typing.Optional[typing.Callable[
        [ModelParameter, typing.Set[str]], typing.Optional[Offer]]] = None
    facts: typing.Tuple[Fact, ...] = ()
    recurrent: typing.Any = None
    carried: typing.Optional[typing.Callable[[ModelParameter], int]] = None


_NOTHING = Layer()


def _declared(name: str) -> Layer:
    from .frontend import LAYER_FUNCTIONS
    return getattr(LAYER_FUNCTIONS.get(name), "declares", _NOTHING)


def _walk(blocks):
    for block in blocks:
        for layer in block.layer:
            name, *extras = layer.split("-")
            yield name, set(extras), _declared(name)


def layers(params: ModelParameter):
    """``(name, {flags}, declaration)`` of every layer of one depth-unit."""
    return _walk(params.block_config)


def _offers(params: ModelParameter, kind: str, blocks):
    for _, extras, spec in _walk(blocks):
        offer = spec.offer(params, extras) if spec.offer is not None else None
        if offer is not None and offer.kind == kind:
            yield offer


def offers(params: ModelParameter, kind: str) -> typing.List[Offer]:
    """What the layers of one depth-unit offer of ``kind``, in execution
    order."""
    return list(_offers(params, kind, params.block_config))


def block_offers(params: ModelParameter, kind: str
                 ) -> typing.List[typing.List[Offer]]:
    """What each block of one depth-unit offers of ``kind``: a list a block,
    in execution order."""
    return [list(_offers(params, kind, (block,)))
            for block in params.block_config]


def region_offers(params: ModelParameter, kind: str
                  ) -> typing.List[typing.List[Offer]]:
    """What the block of every ``jax.checkpoint`` region of the step offers
    of ``kind``, a list a region in execution order: the body's depth-unit
    each time the step runs it (``depth`` x ``loop_steps``, a looped model's
    passes outermost), then the blocks of the multi-token-prediction module
    (``mtp_depth`` x ``mtp_block_config``, model/mtp.py)."""
    module = [list(_offers(params, kind, (block,)))
              for block in params.mtp_block_config]
    return block_offers(params, kind) * (params.depth * params.loop_steps) \
        + module * params.mtp_depth


def step_offers(params: ModelParameter, kind: str):
    """``(offer, times it runs)`` of every layer of the step that offers
    ``kind``: the leading and trailing blocks once, the body ``depth``
    times, a multi-token-prediction module's blocks ``mtp_depth`` times."""
    for blocks, times in ((params.input_block_config, 1),
                          (params.block_config, params.depth),
                          (params.output_block_config, 1),
                          (params.mtp_block_config, params.mtp_depth)):
        for offer in _offers(params, kind, blocks):
            yield offer, times


def _registered() -> typing.List[Layer]:
    from .frontend import DECLARING, LAYER_FUNCTIONS
    found = (getattr(fn, "declares", None)
             for fn in (*LAYER_FUNCTIONS.values(), *DECLARING))
    return [spec for spec in found if spec is not None]


def stats() -> typing.Dict[str, Stat]:
    """``{step metric: its declaration}`` of every registered layer."""
    return {stat.name: stat for spec in _registered() for stat in spec.stats}


def facts() -> typing.List[Fact]:
    """Every registered layer's start-up facts, once each, in line order."""
    found = {fact.metric: fact for spec in _registered()
             for fact in spec.facts}
    return sorted(found.values(), key=lambda fact: fact.place)


def carried_bytes(params: ModelParameter) -> int:
    """Bytes of every carried side value of the step, all declaring layers:
    held across the backward beside the block inputs (model/remat.py)."""
    return sum(spec.carried(params) for spec in _registered()
               if spec.carried is not None)


def fold_stats(layer_stats: typing.Optional[dict]) -> typing.Dict[str, typing.Any]:
    """The step metrics of what the layers reported
    (``LossInfo.layer_stats``): each declared statistic whose key a layer
    appended, folded over the layers."""
    layer_stats = layer_stats or {}
    out: typing.Dict[str, typing.Any] = {}
    for spec in _registered():
        done: typing.Dict[str, typing.Any] = {}
        for stat in spec.stats:
            key = stat.key or stat.name
            if key not in layer_stats:
                continue
            done[stat.name] = _FOLDS[stat.fold](layer_stats[key]) \
                if isinstance(stat.fold, str) else stat.fold(layer_stats, done)
        for stat in spec.stats:
            if stat.label and stat.name in done:
                done.update({f"{stat.name}/{i}": value for i, value
                             in enumerate(done.pop(stat.name))})
        out.update(done)
    return out
