"""Paged KV cache with radix prefix sharing (docs/SERVING.md 'Paged KV').

The slot engine (``infer/engine.py``) reserves ``slots x worst-case-length``
KV rows on device — every slot owns a full-sequence stripe of every cache
leaf whether it holds a 4-token ping or a 4k-token document, and every
admission re-prefills its whole prompt even when co-served requests share a
system prompt.  This module replaces the fixed stripes with a BLOCK POOL:

* **block pool** — each cache leaf with a full sequence axis is re-laid-out
  as ``[num_blocks, block_tokens, ...]`` (slot axis -> physical blocks, seq
  axis -> block-local rows).  A host-side free list + refcounts
  (:class:`BlockPool`) hand blocks to requests as their decode extent
  grows, so device KV memory tracks LIVE tokens; the slot recycler's
  per-leaf row-zeroing becomes block alloc/free.  Leaves without a full
  sequence axis (cumsum totals, conv windows — sequence-RECURRENT state)
  stay resident per slot exactly as in the slot engine.
* **per-slot block tables** — the donated chunk step takes int32
  ``[slots, seq_blocks]`` READ and WRITE tables.  At chunk entry every
  paged leaf is gathered into per-slot full-length views
  (``model/decode.py gather_blocks``; unmapped entries read ZEROS — the
  paged analogue of the slot engine's cleared rows), the UNCHANGED engine
  loop (``engine._engine_loop`` — one definition, so paged-vs-plain greedy
  bit-parity holds by construction) runs its iterations on the views, and
  the views scatter back through the write table (``scatter_blocks``;
  read-only shared blocks DROP).  The pool leaves ride the donated carry
  and alias input->output (HLO-audited as ``paged_chunk_step``).
* **radix prefix sharing** — a radix tree (:class:`RadixIndex`) over
  prompt-token block keys.  An admitted prompt that matches a cached path
  REFERENCES the shared blocks (read table -> shared id, write table ->
  unmapped) and starts decoding at the divergence point: prefill is
  skipped over the shared span, so a prefix-hit TTFT collapses to one
  chunk.  A partial match inside a block is COPY-ON-WRITE: the read table
  points at the shared parent block, the write table at a fresh private
  block — the chunk's gather/scatter round-trip IS the copy, and the
  parent block is never written (tests pin it bit-unchanged).  Finished
  requests return their private blocks; fully-walked prompt blocks are
  promoted into the tree (refcount-0 -> LRU-evictable cache) for future
  hits.  Sharing needs every position-indexed leaf to be paged, so models
  carrying sequence-recurrent caches page WITHOUT sharing (their recurrent
  state cannot be restored at a nonzero admission position).

Correctness notes.  Shared rows hold exactly the KV a cold walk would
write (decode is deterministic in tokens+position, including the int8
per-row quantization), stale rows in freshly-allocated blocks sit strictly
ABOVE every live position and are causally masked until overwritten (the
slot engine's own self-heal argument), and the admit splice zeroes the
admitted slot's view rows at/past the shared length — with sharing off
that is the slot engine's uniform clear, bit for bit.  Greedy parity with
the plain engine, including admission into reclaimed (dirty) blocks and
prefix-hit admissions, is pinned token-for-token by tests/paged_kv_test.py.

``BlockPool`` and ``RadixIndex`` are deliberately device-free (stdlib +
numpy, no jax import) so the block-lifecycle state machine tests run
without device work — the ``infer/scheduler.py`` idiom.
"""
from __future__ import annotations

import collections
import typing

import numpy as np

from .engine import Engine, EngineExecutor, SpecEngineExecutor


# --------------------------------------------------------------- block pool

class BlockPool:
    """Physical-block accounting: free list, per-block slot refcounts, and
    admission reservations.  Blocks are abstract ids ``0..num_blocks-1``;
    the device-side pools are indexed by them through the block tables.

    States: *free* (on the free list), *live* (refcount >= 1, referenced
    by at least one resident slot's table), *cached* (refcount 0 but still
    holding radix-tree content — reclaimable on demand).  Double-frees and
    deref-below-zero raise — a refcount bug silently corrupts co-served
    requests, so the negative control is a hard error."""

    def __init__(self, num_blocks: int):
        self.num_blocks = int(num_blocks)
        self._free: typing.Deque[int] = collections.deque(
            range(self.num_blocks))
        self._on_free = [True] * self.num_blocks
        self._ref = [0] * self.num_blocks
        self.reserved_total = 0

    # -- lifecycle -----------------------------------------------------------

    def alloc(self) -> int:
        """Take a block off the free list with refcount 1; raises
        ``IndexError`` when empty (callers evict or queue — never 500)."""
        b = self._free.popleft()
        self._on_free[b] = False
        self._ref[b] = 1
        return b

    def addref(self, block: int) -> None:
        if self._on_free[block]:
            raise ValueError(f"block {block} is free — addref on a freed "
                             "block is a lifecycle bug")
        self._ref[block] += 1

    def deref(self, block: int) -> int:
        """Drop one reference; returns the remaining count.  Deref of a
        free or zero-ref block raises (the double-free negative control)."""
        if self._on_free[block] or self._ref[block] <= 0:
            raise ValueError(f"double-free of block {block} "
                             f"(ref={self._ref[block]}, "
                             f"free={self._on_free[block]})")
        self._ref[block] -= 1
        return self._ref[block]

    def reclaim(self, block: int) -> None:
        """Return a refcount-0 block to the free list."""
        if self._on_free[block]:
            raise ValueError(f"double-free of block {block} (already on "
                             "the free list)")
        if self._ref[block] != 0:
            raise ValueError(f"reclaim of live block {block} "
                             f"(ref={self._ref[block]})")
        self._on_free[block] = True
        self._free.append(block)

    # -- accounting ----------------------------------------------------------

    def refcount(self, block: int) -> int:
        return self._ref[block]

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def live_count(self) -> int:
        return sum(1 for r in self._ref if r > 0)

    def reserve(self, n: int) -> None:
        self.reserved_total += int(n)

    def unreserve(self, n: int) -> None:
        self.reserved_total = max(0, self.reserved_total - int(n))

    def available(self, evictable: int = 0) -> int:
        """Blocks an admission could still claim: free + cache-evictable,
        minus capacity already promised to admitted-but-growing requests."""
        return self.free_count + int(evictable) - self.reserved_total


# --------------------------------------------------------------- radix tree

class _Node:
    __slots__ = ("key", "block", "children", "parent", "touch")

    def __init__(self, key, block, parent):
        self.key = key          # tuple of block_tokens prompt tokens
        self.block = block      # physical block id (None for the root)
        self.children: typing.Dict[tuple, "_Node"] = {}
        self.parent = parent
        self.touch = 0


class RadixIndex:
    """Radix tree over prompt-token BLOCK keys.

    A path from the root spells a prompt prefix in whole blocks; each node
    holds the physical block whose KV rows cover its span.  ``lookup``
    returns the longest cached path for a prompt plus an optional PARTIAL
    match (longest common token prefix against one child's key — the
    copy-on-write divergence point).  Nodes are LRU-stamped on every
    lookup/insert; ``evict_lru`` removes the least-recently-touched
    refcount-0 LEAF and reclaims its block (a referenced child always
    implies a referenced parent — paths are reference-prefixes — so a
    refcount-0 block guarantees a refcount-0 leaf exists)."""

    def __init__(self, block_tokens: int):
        self.block_tokens = int(block_tokens)
        self.root = _Node(None, None, None)
        self._by_block: typing.Dict[int, _Node] = {}
        self._clock = 0

    def _tick(self, node: _Node) -> None:
        self._clock += 1
        node.touch = self._clock

    def holds(self, block: int) -> bool:
        return block in self._by_block

    def __len__(self) -> int:
        return len(self._by_block)

    def evictable_count(self, pool: BlockPool) -> int:
        return sum(1 for b in self._by_block if pool.refcount(b) == 0)

    def lookup(self, tokens: typing.Sequence[int]
               ) -> typing.Tuple[typing.List[_Node],
                                 typing.Optional[_Node], int]:
        """``(full_path_nodes, partial_node, partial_depth)`` for the
        longest cached prefix of ``tokens``; touches matched nodes."""
        toks = [int(t) for t in tokens]
        b = self.block_tokens
        node, full = self.root, []
        i = 0
        while i + b <= len(toks):
            child = node.children.get(tuple(toks[i:i + b]))
            if child is None:
                break
            self._tick(child)
            full.append(child)
            node = child
            i += b
        rest = toks[i:i + b]
        best, depth = None, 0
        for child in node.children.values():
            d = 0
            for a, c in zip(rest, child.key):
                if a != c:
                    break
                d += 1
            if d > depth:
                best, depth = child, d
        if best is not None:
            self._tick(best)
        return full, best, depth

    def insert(self, parent: typing.Optional[_Node], key: tuple,
               block: int) -> _Node:
        """Add ``key -> block`` under ``parent`` (None = root).  If an
        identical child already exists the EXISTING node wins (its block
        is the canonical copy) and the caller's block stays private."""
        parent = parent or self.root
        child = parent.children.get(tuple(key))
        if child is not None:
            self._tick(child)
            return child
        child = _Node(tuple(key), int(block), parent)
        parent.children[child.key] = child
        self._by_block[child.block] = child
        self._tick(child)
        return child

    def evict_lru(self, pool: BlockPool) -> bool:
        """Remove the least-recently-touched refcount-0 leaf and reclaim
        its block; False when nothing is evictable."""
        best = None
        for block, node in self._by_block.items():
            if node.children or pool.refcount(block) != 0:
                continue
            if best is None or node.touch < best.touch:
                best = node
        if best is None:
            return False
        del best.parent.children[best.key]
        del self._by_block[best.block]
        pool.reclaim(best.block)
        return True

    def clear(self) -> None:
        self.root = _Node(None, None, None)
        self._by_block.clear()


# ------------------------------------------------------- leaf classification

def classify_cache_leaves(shapes: typing.Mapping[str, typing.Any],
                          seq: int) -> typing.Dict[str, tuple]:
    """``{leaf_name: (batch_axis, seq_axis_or_None)}`` over a
    ``decode_cache_shapes`` pytree.  The batch (slot) axis follows the
    engine's convention (axis 1 for depth-stacked leaves, else 0); the
    sequence axis is the first full-``seq``-sized axis after it — the
    position ``spread`` writes rows at.  Leaves without one (running sums,
    conv windows) are sequence-recurrent: resident per slot, unpaged, and
    incompatible with prefix sharing."""
    from ..model import blocks as blocks_mod

    info = {}
    for name, s in shapes.items():
        baxis = 1 if name.startswith(blocks_mod.STACKED_CACHE_PREFIX) else 0
        sax = None
        for ax in range(baxis + 1, len(s.shape)):
            if s.shape[ax] == seq:
                sax = ax
                break
        info[name] = (baxis, sax)
    return info


# ------------------------------------------------------------- the executor

class PagedEngineExecutor(EngineExecutor):
    """The slot engine with its KV stripes replaced by the block pool.

    Same executor surface the controller drives (``admit``/``release``/
    ``dispatch``/``tokens``/``reset``) plus ``can_admit`` (the scheduler's
    fits-gate: free-list exhaustion QUEUES instead of erroring) and
    ``pool_stats`` (the /metrics block gauges).  Construction raises
    ``NotImplementedError`` for geometries paging cannot serve (sequence
    not divisible by the block size) — ``kv_paging="auto"`` falls back to
    the plain engine on that signal, ``"on"`` surfaces it."""

    def __init__(self, interface, slots: int,
                 seed: typing.Optional[int] = None,
                 block_tokens: typing.Optional[int] = None,
                 pool_blocks: typing.Optional[int] = None):
        from .sampler import decode_cache_shapes

        super().__init__(interface, slots, seed=seed)
        p = interface.params
        self.block_tokens = int(block_tokens
                                if block_tokens is not None
                                else getattr(p, "kv_block_tokens", 16))
        if self.block_tokens < 1:
            raise ValueError("kv_block_tokens must be >= 1")
        if self.seq % self.block_tokens:
            raise NotImplementedError(
                f"kv_paging needs the sequence length in patches "
                f"({self.seq}) divisible by kv_block_tokens "
                f"({self.block_tokens})")
        self.seq_blocks = self.seq // self.block_tokens
        probe = np.zeros((self.slots, self.seq, self.tps), np.int32)
        shapes = decode_cache_shapes(self.model_w, self.variables, probe)
        self.leaf_info = classify_cache_leaves(shapes, self.seq)
        nb = int(pool_blocks if pool_blocks is not None
                 else getattr(p, "kv_pool_blocks", 0) or 0)
        self.num_blocks = nb or self.slots * self.seq_blocks
        if self.num_blocks < self.seq_blocks:
            raise ValueError(
                f"kv_pool_blocks={self.num_blocks} cannot hold even one "
                f"full-length request ({self.seq_blocks} blocks)")
        # prefix sharing needs EVERY position-indexed leaf paged: a
        # sequence-recurrent resident leaf (cumsum/conv window) cannot be
        # restored at a nonzero admission position, so such models page
        # without sharing (admissions always walk their full prompt)
        self.sharing = all(sax is not None
                           for _, sax in self.leaf_info.values())
        self.tree = RadixIndex(self.block_tokens) if self.sharing else None
        self.pool = BlockPool(self.num_blocks)
        self.SENTINEL = self.num_blocks
        self.rtable = np.full((self.slots, self.seq_blocks), self.SENTINEL,
                              np.int32)
        self.wtable = np.full((self.slots, self.seq_blocks), self.SENTINEL,
                              np.int32)
        self._keep_len = np.zeros(self.slots, np.int32)
        self._owned: typing.List[set] = [set() for _ in range(self.slots)]
        self._shared: typing.List[list] = [[] for _ in range(self.slots)]
        self._reserved = [0] * self.slots
        #: per-slot promotion cursor: (tree node to insert under, next
        #: block index to consider)
        self._promo: typing.List[typing.Optional[tuple]] = \
            [None] * self.slots
        self._prompt_toks: typing.List[typing.Optional[np.ndarray]] = \
            [None] * self.slots
        self.stats = {"prefix_lookups": 0, "prefix_hits": 0,
                      "prefix_hit_tokens": 0, "cow_copies": 0,
                      "tree_evictions": 0}
        # the RESIDENT device footprint (the number the occupancy gauges
        # are about): paged leaves at pool scale + the per-slot recurrent
        # leaves — not slots x worst-case length
        ratio = self.num_blocks / float(self.slots * self.seq_blocks)
        self.cache_bytes = 0
        for n, s in shapes.items():
            bytes_ = int(np.prod(s.shape)) * s.dtype.itemsize
            _, sax = self.leaf_info[n]
            self.cache_bytes += int(bytes_ * ratio) if sax is not None \
                else bytes_
        # recompose with the block tables on top of the plain slots
        self.engine = Engine(self.model_w, self.mesh,
                             paged=(self.block_tokens, self.num_blocks))

    # -- block bookkeeping ---------------------------------------------------

    def _alloc_block(self, slot: int) -> int:
        """One block for ``slot``: free list first, then LRU eviction of
        refcount-0 tree leaves.  Reservations made at admission guarantee
        this succeeds for admitted requests."""
        while self.pool.free_count == 0:
            if self.tree is None or not self.tree.evict_lru(self.pool):
                raise RuntimeError(
                    "KV block pool exhausted with nothing evictable — "
                    "admission reservations should have prevented this")
            self.stats["tree_evictions"] += 1
        b = self.pool.alloc()
        self._owned[slot].add(b)
        if self._reserved[slot] > 0:
            self._reserved[slot] -= 1
            self.pool.unreserve(1)
        return b

    def _free_slot_blocks(self, slot: int) -> None:
        """Drop the slot's references.  Shared blocks deref (the parent /
        tree copy lives on); private blocks return to the free list unless
        they were promoted into the radix tree, where they stay as
        refcount-0 reusable cache.  Exactly the non-shared, non-promoted
        count lands back on the free list (tests pin it)."""
        for b in self._shared[slot]:
            if self.pool.deref(b) == 0 and not (self.tree is not None
                                                and self.tree.holds(b)):
                self.pool.reclaim(b)
        self._shared[slot] = []
        for b in self._owned[slot]:
            if self.pool.deref(b) == 0 and not (self.tree is not None
                                                and self.tree.holds(b)):
                self.pool.reclaim(b)
        self._owned[slot] = set()
        self.pool.unreserve(self._reserved[slot])
        self._reserved[slot] = 0
        self.rtable[slot, :] = self.SENTINEL
        self.wtable[slot, :] = self.SENTINEL
        self._keep_len[slot] = 0
        self._promo[slot] = None
        self._prompt_toks[slot] = None

    def _blocks_needed(self, prompt_len: int, end: int, toks) -> int:
        """Worst-case private blocks a request can come to own: blocks
        through its last written row, minus fully-shared ones."""
        if end <= 1:
            return 0
        shared_full = 0
        if self.tree is not None and prompt_len > 1:
            full, _, _ = self.tree.lookup(toks[:prompt_len - 1])
            shared_full = len(full)
        return max(0, (end - 1) // self.block_tokens + 1 - shared_full)

    # -- scheduler surface ---------------------------------------------------

    def can_admit(self, req) -> bool:
        """The scheduler's fits-gate: False keeps the request QUEUED (the
        slot-exhaustion semantics, extended to block exhaustion) instead
        of failing it."""
        toks = np.asarray(req.toks, np.int64).reshape(-1)[:self.seq - 1]
        need = self._blocks_needed(len(toks), req.end_pos(self.seq), toks)
        evictable = (self.tree.evictable_count(self.pool)
                     if self.tree is not None else 0)
        return self.pool.available(evictable) >= need

    def admit(self, slot: int, req) -> None:
        super().admit(slot, req)
        self._free_slot_blocks(slot)  # defensive: release() already ran
        toks = np.asarray(req.toks, np.int64).reshape(-1)[:self.seq - 1]
        plen = len(toks)
        end = int(self.end_pos[slot])
        need = self._blocks_needed(plen, end, toks)
        self.pool.reserve(need)
        self._reserved[slot] = need
        self._prompt_toks[slot] = toks
        full_nodes: typing.List[_Node] = []
        partial, depth = None, 0
        if self.tree is not None and plen > 1:
            # match at most plen-1 tokens: the decode must still run at
            # least one step (reading the last prompt token) to generate,
            # and capping here keeps every shared row child-valid
            full_nodes, partial, depth = self.tree.lookup(toks[:plen - 1])
            self.stats["prefix_lookups"] += 1
        shared_len = len(full_nodes) * self.block_tokens + depth
        for bi, node in enumerate(full_nodes):
            self.pool.addref(node.block)
            self._shared[slot].append(node.block)
            self.rtable[slot, bi] = node.block
            self.wtable[slot, bi] = self.SENTINEL  # read-only: never written
        if depth > 0:
            # copy-on-write at the divergence point: read the shared parent
            # block, write a fresh private one — the chunk's gather/scatter
            # round-trip performs the copy, the parent stays bit-unchanged
            bi = len(full_nodes)
            self.pool.addref(partial.block)
            self._shared[slot].append(partial.block)
            own = self._alloc_block(slot)
            self.rtable[slot, bi] = partial.block
            self.wtable[slot, bi] = own
            self.stats["cow_copies"] += 1
        if shared_len:
            self.stats["prefix_hits"] += 1
            self.stats["prefix_hit_tokens"] += shared_len
        self._keep_len[slot] = shared_len
        self.q[slot] = shared_len  # prefill skipped over the shared span
        self._promo[slot] = (full_nodes[-1] if full_nodes else None,
                             len(full_nodes))

    def release(self, slot: int) -> None:
        super().release(slot)
        self._free_slot_blocks(slot)

    # -- dispatch ------------------------------------------------------------

    def _ensure_blocks(self, steps: int) -> None:
        """Map private blocks through every live slot's write extent for
        this chunk (incremental allocation — in-use blocks track live
        tokens, not slots x worst-case)."""
        for s in range(self.slots):
            end = int(min(self.end_pos[s], self.seq))
            if end <= 0:
                continue
            hi = min(int(self.q[s]) + int(steps), end - 1)
            for bi in range(hi // self.block_tokens + 1):
                if self.rtable[s, bi] == self.SENTINEL:
                    b = self._alloc_block(s)
                    self.rtable[s, bi] = b
                    self.wtable[s, bi] = b

    def _promote_prompt_blocks(self) -> None:
        """Insert fully-walked prompt blocks into the radix tree so future
        admissions can hit them.  A block is promotable once every row in
        it has been written (q past its end) and its span lies entirely
        within the prompt (rows derived from known tokens, not generated
        ones)."""
        if self.tree is None:
            return
        bt = self.block_tokens
        for s in range(self.slots):
            if self._promo[s] is None or int(self.end_pos[s]) <= 0:
                continue
            node, bi = self._promo[s]
            toks = self._prompt_toks[s]
            plen = 0 if toks is None else len(toks)
            q = int(self.q[s])
            while (bi + 1) * bt <= min(plen, q):
                block = int(self.wtable[s, bi])
                if block == self.SENTINEL:
                    break  # shared span (shouldn't happen past the cursor)
                key = tuple(int(t) for t in toks[bi * bt:(bi + 1) * bt])
                node = self.tree.insert(node, key, block)
                bi += 1
            self._promo[s] = (node, bi)

    def dispatch(self, steps: int) -> np.ndarray:
        jnp = self._jnp
        self._ensure_blocks(steps)
        phase = ("init" if self._carry is None else
                 "admit" if self._admit_mask.any() else "plain")
        fn = self.engine.step(phase)
        fargs = (jnp.asarray(self.top_k), jnp.asarray(self.top_p),
                 jnp.asarray(self.rep))
        if phase == "init":
            seen = jnp.zeros((self.slots, self.params_w.vocab_size),
                             jnp.float32)
            carry = (jnp.zeros(self.slots, jnp.int32),
                     jnp.asarray(self._token_host), self._key0, seen)
        else:
            carry = self._carry
        admit_args = ()
        if phase != "plain":
            admit_args = (jnp.asarray(self._admit_mask),
                          jnp.asarray(self._admit_rows),
                          jnp.asarray(self._keep_len))
        out = fn(self.variables, jnp.asarray(self.ipb), jnp.asarray(self.tb),
                 jnp.asarray(self.end_pos), jnp.int32(int(steps)), fargs,
                 admit_args, jnp.asarray(self.rtable),
                 jnp.asarray(self.wtable), carry)
        q, token_x = out[0], out[1]
        self._carry = out
        self._token_host = np.asarray(token_x)
        self.q = np.asarray(q).astype(np.int64)
        self._admit_mask[:] = False
        # the write-back landed: from now on read every written block from
        # its private copy (this is what completes a COW — the next gather
        # must see the child's rows, not the parent's)
        written = self.wtable != self.SENTINEL
        self.rtable[written] = self.wtable[written]
        self._promote_prompt_blocks()
        return self.q

    def reset(self) -> None:
        """Failed-dispatch recovery: the donated carry (pool included) is
        gone, so every block mapping and the whole radix cache with it."""
        super().reset()
        self.pool = BlockPool(self.num_blocks)
        if self.tree is not None:
            self.tree.clear()
        self.rtable[:, :] = self.SENTINEL
        self.wtable[:, :] = self.SENTINEL
        self._keep_len[:] = 0
        self._owned = [set() for _ in range(self.slots)]
        self._shared = [[] for _ in range(self.slots)]
        self._reserved = [0] * self.slots
        self._promo = [None] * self.slots
        self._prompt_toks = [None] * self.slots

    # -- KV transfer (infer/kv_transfer.py, docs/SERVING.md 'Disaggregated
    # tier'): host handles on the donated carry's pool leaves, so block
    # streaming between replicas reads/writes them WITHOUT a new jit site

    def transfer_pools(self) -> typing.Optional[dict]:
        """``{poolset: (pools_dict, leaf_info)}`` of the live carry's
        block-pool leaves, or None before the first dispatch (the pools
        are built inside the donated init trace)."""
        if self._carry is None:
            return None
        # paged carry layout: (q, token_x, pools, key, seen)
        return {"target": (self._carry[2], self.leaf_info)}

    def set_transfer_pools(self, poolsets: dict) -> None:
        """Swap updated pool leaves back into the carry (eager ``.at[]``
        writes happened outside the donated programs)."""
        carry = list(self._carry)
        carry[2] = poolsets["target"]
        self._carry = tuple(carry)

    # -- observability -------------------------------------------------------

    def pool_stats(self) -> dict:
        """The /metrics block gauges (docs/OBSERVABILITY.md): occupancy
        that proves device KV memory tracks live tokens, plus the sharing
        economics (hits, shared tokens, COW copies, evictions)."""
        cached = (self.tree.evictable_count(self.pool)
                  if self.tree is not None else 0)
        return {
            "blocks_total": self.num_blocks,
            "blocks_free": self.pool.free_count,
            "blocks_in_use": self.pool.live_count,
            "blocks_cached": cached,
            "blocks_reserved": self.pool.reserved_total,
            "block_tokens": self.block_tokens,
            "sharing": self.sharing,
            **self.stats,
        }


# ------------------------------------------------- the composed deployment

class SpecPagedEngineExecutor(SpecEngineExecutor, PagedEngineExecutor):
    """Spec-on-paged: draft-and-verify running over the block pool — the
    ``spec_paged_chunk_step`` composition, assembled from the two
    components rather than written as a fourth program.

    The draft model's cache leaves page onto the SAME block tables as the
    target's (one logical block space, two physical pools): a draft KV row
    is deterministic in tokens+position exactly like a target row, so a
    prefix-hit admission resumes the draft from the shared span too, COW
    divergence copies both pools through the same gather/scatter
    round-trip, and rejected draft rows in both pools self-heal
    left-to-right before the next round reads them (the rollback-by-
    overwrite argument, unchanged).  Because the spec probe already refuses
    sequence-recurrent caches (both models), every leaf of both pools is
    pageable — the composed deployment always has prefix sharing.

    Construction raises ``NotImplementedError`` on either component's
    refusal signal (draft geometry, recurrent caches, block divisibility)
    so ``auto`` knobs can fall back component-wise; greedy parity with the
    plain slot engine through prefix-hit admission, mid-draft COW
    divergence, and total-rejection rounds is pinned token-for-token by
    tests/spec_paged_test.py."""

    def __init__(self, interface, slots: int, draft,
                 seed: typing.Optional[int] = None,
                 draft_tokens: typing.Optional[int] = None,
                 min_accept_rate: typing.Optional[float] = None,
                 block_tokens: typing.Optional[int] = None,
                 pool_blocks: typing.Optional[int] = None):
        # the two init halves run in sequence, mirroring the carry: the
        # paged base builds pool/tree/tables (and recomposes the Engine
        # with the block tables), then the spec half stacks the draft pool
        # + accept state on top and recomposes again
        PagedEngineExecutor.__init__(self, interface, slots, seed=seed,
                                     block_tokens=block_tokens,
                                     pool_blocks=pool_blocks)
        self._init_spec(draft, draft_tokens, min_accept_rate)

    def _draft_leaf_info(self) -> typing.Dict[str, tuple]:
        """Leaf classification for the DRAFT pool (its cache geometry,
        not the target's), computed once — kv_transfer streams both pools
        through the shared block tables."""
        cached = getattr(self, "_draft_leaf_info_cache", None)
        if cached is None:
            from .sampler import decode_cache_shapes
            probe = np.zeros((self.slots, self.seq, self.tps), np.int32)
            dshapes = decode_cache_shapes(self.draft_model_w,
                                          self.draft_variables, probe)
            cached = classify_cache_leaves(dshapes, self.seq)
            self._draft_leaf_info_cache = cached
        return cached

    def transfer_pools(self) -> typing.Optional[dict]:
        if not self._spec_enabled:
            return PagedEngineExecutor.transfer_pools(self)
        if self._carry is None:
            return None
        # spec-paged carry layout: (token_x, pools, dpools, key, seen)
        return {"target": (self._carry[1], self.leaf_info),
                "draft": (self._carry[2], self._draft_leaf_info())}

    def set_transfer_pools(self, poolsets: dict) -> None:
        if not self._spec_enabled:
            return PagedEngineExecutor.set_transfer_pools(self, poolsets)
        carry = list(self._carry)
        carry[1] = poolsets["target"]
        carry[2] = poolsets["draft"]
        self._carry = tuple(carry)

    def dispatch(self, steps: int) -> np.ndarray:
        """Acceptance-aware dispatch over the block pool: verify rounds
        like the spec executor, block-table maintenance like the paged one.
        Once self-disabled, ``_to_plain_carry`` has recomposed the Engine
        down to the paged composition and every dispatch delegates there."""
        if not self._spec_enabled:
            return PagedEngineExecutor.dispatch(self, steps)
        jnp = self._jnp
        rounds = max(1, -(-int(steps) // (self.k + 1)))
        for _ in range(rounds):
            # a verify round writes at most k+1 rows past each slot's
            # position: map private blocks through that extent first
            self._ensure_blocks(self.k + 1)
            phase = ("init" if self._carry is None else
                     "admit" if self._admit_mask.any() else "plain")
            fn = self.engine.step(phase)
            if self._dev_args is None:
                self._dev_args = (jnp.asarray(self.ipb),
                                  jnp.asarray(self.tb),
                                  jnp.asarray(self.end_pos),
                                  (jnp.asarray(self.top_k),
                                   jnp.asarray(self.top_p),
                                   jnp.asarray(self.rep)),
                                  jnp.asarray(self._spec_mask))
            ipb_d, tb_d, end_d, fargs, mask_d = self._dev_args
            if phase == "init":
                seen = jnp.zeros((self.slots, self.params_w.vocab_size),
                                 jnp.float32)
                carry = (jnp.asarray(self._token_host), self._key0, seen)
            else:
                carry = self._carry
            admit_args = ()
            if phase != "plain":
                admit_args = (jnp.asarray(self._admit_mask),
                              jnp.asarray(self._admit_rows),
                              jnp.asarray(self._keep_len))
            out = fn(self.variables, self.draft_variables,
                     jnp.asarray(self.q.astype(np.int32)),
                     ipb_d, tb_d, end_d, fargs, mask_d,
                     jnp.asarray(self._fix_tok),
                     jnp.asarray(self._fix_mask),
                     jnp.asarray(self._seen_lo), admit_args,
                     jnp.asarray(self.rtable), jnp.asarray(self.wtable),
                     carry)
            self._carry = out[:5]
            # np.array, not asarray: the accept loop WRITES corrections
            self._token_host = np.array(out[0])
            self._admit_mask[:] = False
            # the write-back landed: read every written block from its
            # private copy from now on (completes COW for BOTH pools —
            # they share the tables)
            written = self.wtable != self.SENTINEL
            self.rtable[written] = self.wtable[written]
            self._accept_round(np.asarray(out[5]))
            self._promote_prompt_blocks()
            if not self._spec_enabled:
                break  # recomposed to paged mid-dispatch: it takes over
            if not np.any((self.end_pos > 0)
                          & (self.q < self.end_pos - 1)):
                break  # every live slot reached its end
        return self.q
