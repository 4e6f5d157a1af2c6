"""The stash channel's consumer side: how a layer or a kernel wrapper asks
whether its output rides a memory strategy's residuals, and hands it over.

``model/blocks.py`` makes the channels (collect / provide / name) and owns
the ordering contract; what is here is all a consumer touches, so
``parallel/`` imports downwards only."""
from __future__ import annotations

import typing


def stash_channel(ctx, kind: str) -> typing.Optional[dict]:
    """The scope context's channel if it carries ``kind``, else None —
    how a layer asks whether its output rides the residuals."""
    chan = getattr(ctx, "replay_stash", None)
    return chan if chan is not None and kind in chan["kinds"] else None


def stash_push(chan, item) -> None:
    """Consumer-side half of the stash-channel contract (collect mode) —
    the single definition every consumer shares (flash and ring attention,
    the bottleneck in-projection)."""
    chan["items"].append(item)


def stash_pop(chan):
    """Consumer-side half of the stash-channel contract (provide mode):
    the next item in push order."""
    item = chan["items"][chan["i"]]
    chan["i"] += 1
    return item


def stash_collecting(chan) -> bool:
    return chan is not None and chan["mode"] == "collect"


def stash_naming(chan) -> bool:
    return chan is not None and chan["mode"] == "name"
