"""The KV pool for the serving engine: ref-counted pages.

:class:`PagedSlotPool` is the one layout serving has. A model declares,
layer by layer, what it caches and in which GROUP the layer's entries
live. There are three kinds of group:

- the GROWING group (``"global"``): a table of ``max_len / block_size``
  entries a slot, blocks of tokens bound as positions advance;
  everything below describes it;
- a WINDOW group (``"window"``), for layers that attend a sliding window
  only: a RING of ``ceil(window / block_size) + 1`` entries a slot,
  bound once when the slot is taken and released with it; position
  ``p`` lives in entry ``(p // block_size) % ring``, so a block is
  overwritten once its tokens are out of every later query's window;
- a STATE group (``"state"``), for layers whose cache is per SEQUENCE
  and not per token (a linear-attention layer's recurrent state and its
  convolutions' tails): ONE entry a slot, bound and released with the
  slot like a ring, never indexed by position, never shared. A ring
  needs no reset because its mask hides stale rows; a state has no
  mask, so it is RESET AT ADMISSION: the prefill program's chunk at
  offset 0 starts from zeros whatever the entry held (no maintenance
  program runs; ``serve.state.resets_total`` counts them).

All three keep their entries in one implementation of free list and ref
counts (:class:`_BlockBooks`; entry 0 of each is its scratch entry). The
scheduler sees
the slot-level surface (``alloc``/``free``/``num_free``/``occupancy``);
the engine sees blocks: per-layer K/V buffers shaped
``[num_blocks, block_size, H*D]`` (lane-dense rows: one row a position,
its heads side by side in lanes, which is the device's own row-major
layout, so no program copies a pool between layouts; PERF.md section 6,
PR 27), a host-side free list of blocks with REF COUNTS, and a per-slot
block table
(``[max_blocks_per_row]`` int32) threaded into the compiled programs.
Admission binds only the blocks the prompt needs and decode binds
further blocks lazily as positions advance, so resident memory tracks
tokens actually written, not ``B_max * max_len``. Block 0 is a
reserved SCRATCH block: freed slots' table rows reset to it and
non-emitting rows' pad writes are routed to it in-program, so a
retired slot can never scribble on a block that was rebound to a new
request.

On top of the ref counts the paged pool keeps a **prefix-reuse trie**
(:class:`PrefixTrie`) keyed on full blocks of prompt tokens: a request
whose prompt prefix matches cached blocks takes REFERENCES on them
instead of re-prefilling (TTFT collapses for templated traffic), and
the trie itself holds one reference per cached block so the cache
survives its donor's retirement. Writes go through
:meth:`PagedSlotPool.prepare_write`, which enforces the single
invariant everything else leans on: **a block is only ever written
while its ref count is exactly 1**. A write into a shared block
(ref > 1 — a cached prefix, or a donor's block another request now
references) first COPIES it to a fresh block and swaps the writer's
table entry (copy-on-write, counted in ``serve.kv.cow_copies_total``).
When the free list runs dry, trie-only blocks (ref == 1, held by the
cache alone) are evicted LRU-first; past that, binding raises the typed
:class:`KVBlocksExhausted` — the scheduler's backpressure signal, never
a crash. The ``serve.kv.bind`` fault point arms the same path for
chaos plans.

**Host tier** (``ServeConfig.kv_host_blocks``, int8 pools only): LRU
eviction normally *discards* a cached block, so a chat user returning
for turn N+1 after device blocks cycle pays a full cold prefill. With
a host budget configured, an evicted trie block is DEMOTED instead —
its int8 payload + per-(block, head) scales (already the migration
wire format, so the copy is lossless and bit-identical) land in a
host-side LRU keyed by the block's full prompt-prefix token path.
``bind_for_prompt`` then extends a device trie match through the host
tier: consecutively host-cached blocks past the device-matched prefix
are PROMOTED back — fresh ref == 1 allocations (the write invariant
holds by construction), the wire payload scattered in by the same
device op migration installs with, the blocks re-indexed in the trie,
and the requesting slot referencing them like any other prefix hit.
The scatter is DISPATCHED before any host bookkeeping (the engine's
``copy_to_host_async``-then-bookkeep idiom, reversed), so the bucketed
prefill chunks that follow queue behind the host→device copy instead
of the host ever blocking on it — promotion is pure data movement and
adds NO compiled programs. Promotion is exclusive (the host entry
moves, it is not copied), a failed promote (pool exhausted mid-alloc,
or the ``serve.kv.promote`` fault point) degrades to a cold prefill —
typed, counted, never an error surfaced to the request — and
``leak_check`` audits the host tier's books (entry count vs budget,
byte accounting, per-entry geometry) next to the device ref counts.
At int8, host RAM holds ~100x the device's resident conversations —
this is what makes shared-prefix reuse survive real multi-tenant
churn instead of only back-to-back templated bursts.

Stale-KV reuse invariant (regression-tested on the float and the int8
pool): freeing a slot/block is bookkeeping only — stale K/V stays in
the buffers, and that is safe by construction because a new occupant's prefill
overwrites ``[0, prompt_len)`` (or takes references to blocks holding
EXACTLY the tokens it would have written) before attention ever covers
those positions, and the decode path (mask or flash-decode ``lengths``)
stops at ``pos``. Bucket pads beyond the prompt write garbage K/V above
``prompt_len`` that the first decode writes overwrite before any mask
reaches them. Non-emitting rows in a decode block write one pad token's
K/V at their FROZEN position each scan step (their own bound block,
or scratch when inactive) — never attended, because the row's own
``lengths`` stop at its content.
"""

from __future__ import annotations

import collections
import itertools
import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from nezha_tpu import faults, obs
from nezha_tpu.ops import quant
from nezha_tpu.ops.pallas.decode_attention import ring_entries


class KVBlocksExhausted(RuntimeError):
    """Typed backpressure: a KV block bind found no free block (after
    eviction). Carries the ``slot`` that was being grown (None during
    admission binds). The scheduler retires/requeues instead of
    crashing the decode loop."""

    def __init__(self, msg: str, slot: Optional[int] = None):
        super().__init__(msg)
        self.slot = slot


# --------------------------------------------------------------- paged
class _TrieNode:
    __slots__ = ("tokens", "block", "children", "parent", "tick")

    def __init__(self, tokens: Tuple[int, ...], block: int,
                 parent: Optional["_TrieNode"], tick: int):
        self.tokens = tokens
        self.block = block
        self.children: Dict[Tuple[int, ...], "_TrieNode"] = {}
        self.parent = parent
        self.tick = tick


class PrefixTrie:
    """Prefix-reuse index over FULL blocks of prompt tokens.

    Each node is one cached block keyed by the exact ``block_size``
    token tuple it holds, childed under the node for the preceding
    block — so a root-to-node path spells a prompt prefix. Only full
    blocks are indexed: a full block is never written again (writes
    happen at positions past it), so cached content is immutable by
    construction and lookups never race writers. The trie holds ONE
    pool reference per node; eviction (leaf-first, LRU by touch tick)
    drops that reference, freeing the block once no request holds it.
    """

    def __init__(self, block_size: int):
        self.block_size = block_size
        self.root: Dict[Tuple[int, ...], _TrieNode] = {}
        self._nodes: set = set()
        # Leaves maintained incrementally: eviction candidates are
        # found in O(|leaves|) instead of scanning every node — the
        # reclaim path runs under memory pressure on the per-dispatch
        # binding path, where an O(nodes) scan per freed block would
        # bite exactly when the pool is fullest.
        self._leaves: set = set()
        self._tick = itertools.count()

    def __len__(self) -> int:
        return len(self._nodes)

    @property
    def blocks(self) -> List[int]:
        return [n.block for n in self._nodes]

    def match(self, tokens: Sequence[int]) -> List[int]:
        """-> block ids of the longest cached full-block prefix of
        ``tokens`` (possibly empty). Touches matched nodes (LRU)."""
        bs = self.block_size
        out: List[int] = []
        children = self.root
        i = 0
        while i + bs <= len(tokens):
            node = children.get(tuple(int(t) for t in tokens[i:i + bs]))
            if node is None:
                break
            node.tick = next(self._tick)
            out.append(node.block)
            children = node.children
            i += bs
        return out

    def insert(self, tokens: Sequence[int], blocks: Sequence[int],
               take_ref) -> int:
        """Index the full-block prefix of ``tokens`` under ``blocks``
        (the slot's bound block ids, one per block of the prompt).
        ``take_ref(block)`` is called once per NEWLY inserted node (the
        trie's own reference). Existing nodes (same token path) are
        kept — first writer wins, later identical content just
        refreshes the LRU tick. -> number of nodes inserted."""
        bs = self.block_size
        children = self.root
        parent: Optional[_TrieNode] = None
        inserted = 0
        for bi in range(len(tokens) // bs):
            key = tuple(int(t) for t in tokens[bi * bs:(bi + 1) * bs])
            node = children.get(key)
            if node is None:
                node = _TrieNode(key, int(blocks[bi]), parent,
                                 next(self._tick))
                children[key] = node
                self._nodes.add(node)
                self._leaves.add(node)
                if parent is not None:
                    self._leaves.discard(parent)
                take_ref(node.block)
                inserted += 1
            else:
                node.tick = next(self._tick)
            parent = node
            children = node.children
        return inserted

    def evict(self, want: int, release, only=None,
              on_evict: Optional[Callable] = None) -> int:
        """Drop up to ``want`` cached blocks, leaf-first and LRU-first
        within the leaves (a parent only becomes evictable once its
        children are gone — evicting an interior node would orphan the
        path below it). ``only(block)``, when given, filters the
        candidates — the pool passes "ref count is exactly 1" so
        eviction only ever destroys entries whose release actually
        FREES a block (a leaf still bound by a live prefix-hit request
        would free nothing). ``on_evict(path_tokens, block)``, when
        given, runs for each victim BEFORE its release, with the full
        root-to-node token path — the pool's host-tier demotion hook
        (the block still holds the node's content here: full blocks
        are immutable and ref == 1 means nobody else can write it).
        ``release(block)`` drops the trie's reference. -> nodes
        actually evicted."""
        evicted = 0
        while evicted < want:
            leaves = [n for n in self._leaves
                      if only is None or only(n.block)]
            if not leaves:
                break
            victim = min(leaves, key=lambda n: n.tick)
            self._remove(victim)
            if on_evict is not None:
                on_evict(self._path_tokens(victim), victim.block)
            release(victim.block)
            evicted += 1
        return evicted

    @staticmethod
    def _path_tokens(node: _TrieNode) -> Tuple[int, ...]:
        """The full root-to-``node`` token path — the prompt prefix
        whose K/V the node's block (with its ancestors') holds. The
        host tier keys on this, never on the node's own block tokens
        alone: a block's content depends on every preceding token."""
        parts: List[Tuple[int, ...]] = []
        while node is not None:
            parts.append(node.tokens)
            node = node.parent
        return tuple(t for tok in reversed(parts) for t in tok)

    def clear(self, release) -> int:
        """Drop every cached block (the ``prefix_cache`` off-switch /
        test teardown). -> count dropped."""
        n = len(self._nodes)
        for node in self._nodes:
            release(node.block)
        self.root = {}
        self._nodes = set()
        self._leaves = set()
        return n

    def _remove(self, node: _TrieNode) -> None:
        siblings = node.parent.children if node.parent else self.root
        siblings.pop(node.tokens, None)
        self._nodes.discard(node)
        self._leaves.discard(node)
        if node.parent is not None and not node.parent.children:
            self._leaves.add(node.parent)


class _BlockBooks:
    """One cache group's blocks: a LIFO free list over ``1 .. n-1``
    (block 0 is the group's scratch block: never allocated, never
    ref-counted) and per-block ref counts. The growing group, the
    window ring and the state group each have one; the lifecycle is
    this class's."""

    def __init__(self, num_blocks: int):
        self.num_blocks = num_blocks
        self.free: List[int] = list(range(num_blocks - 1, 0, -1))
        self.refs = np.zeros((num_blocks,), np.int64)

    @property
    def used(self) -> int:
        return self.num_blocks - 1 - len(self.free)

    def take(self) -> int:
        """Pop a free block at ref count 1 (the caller saw one free)."""
        b = self.free.pop()
        self.refs[b] = 1
        return b

    def release(self, block: int) -> bool:
        """Drop one reference; -> True when that freed the block."""
        if block == 0:
            return False
        self.refs[block] -= 1
        if self.refs[block] < 0:
            raise AssertionError(
                f"block {block} ref count went negative (double release)")
        if self.refs[block] == 0:
            self.free.append(block)
            return True
        return False

    def check(self, expect: np.ndarray, what: str) -> None:
        """Assert the books balance against ``expect``ed ref counts."""
        if not np.array_equal(expect, self.refs):
            bad = np.flatnonzero(expect != self.refs)
            raise AssertionError(
                f"{what} ref-count leak at blocks {bad.tolist()}: "
                f"expected {expect[bad].tolist()}, "
                f"recorded {self.refs[bad].tolist()}")
        n_held = int(np.count_nonzero(self.refs))
        if len(self.free) + n_held != self.num_blocks - 1:
            raise AssertionError(
                f"{what} leak: {len(self.free)} free + {n_held} held != "
                f"{self.num_blocks - 1} allocatable")


def _copy_block(caches: list, src, dst) -> list:
    """Device-side block copy across every layer's K and V pool:
    ``caches[l][kv] [N, bs, H*D]`` with block ``src`` copied over
    block ``dst``. The COW move. Leading-axis tree_map means every
    block-indexed leaf moves together — int8 pools' ``[N, H]`` scale
    rows copy with their blocks in the same call (the "a block and its
    scale row move together" invariant). Jitted once per pool shape (src/dst
    cross as 0-d arrays so indices never recompile); donation makes it
    an in-place rewrite of one block, not a pool copy. Deliberately NOT
    routed through the engine executor: the frozen-program contract
    ("1 step + len(prefill_buckets) entries") is pinned on the
    executor's cache, and COW is pool maintenance, not a serving
    program."""
    def leaf(x):
        blk = lax.dynamic_slice_in_dim(x, src, 1, axis=0)
        return lax.dynamic_update_slice_in_dim(x, blk, dst, axis=0)

    return jax.tree_util.tree_map(leaf, caches)


_copy_block_jit = jax.jit(_copy_block, donate_argnums=(0,))


# ------------------------------------------------- migration device ops
# Block export/install for cross-replica KV migration (serve/migrate.py
# carries the wire; the router orchestrates). Like _copy_block these are
# pool maintenance, deliberately NOT routed through the engine executor:
# the frozen-program contract is pinned on the executor's cache, and a
# migration is not a serving program. jax.jit keys on the index shape,
# so one program per distinct block count — block counts are small and
# bounded by blocks_per_slot.
# The WIRE carries per-head tiles, ``[n, H, bs, D]`` int8 + ``[n, H]``
# scales (serve/migrate.py; the host tier keeps the same arrays), as it
# did when the pool itself was ``[N, H, bs, D]``. The pool is lane-dense
# now (``[N, bs, H*D]``), so export splits the heads out of the rows and
# install merges them back: a transpose of a few blocks, in programs
# that are not serving programs. Old payloads and other replicas' pulls
# install unchanged.
def _gather_blocks_quantized(caches, idx):
    """int8 pool -> wire: the int8 values and fp32 per-(block, head)
    scales ARE the wire's (only the layout differs), so export is a
    gather — a migrated block lands on the destination bit-identical."""
    out = []
    for layer in caches:
        heads = layer["k_scale"].shape[1]
        entry = {}
        for kv in ("k", "v"):
            entry[kv] = quant.split_heads(
                jnp.take(layer[kv], idx, axis=0), heads)
            entry[f"{kv}_scale"] = jnp.take(layer[f"{kv}_scale"], idx,
                                            axis=0)
        out.append(entry)
    return out


def _gather_quantize_blocks(caches, idx, heads):
    """bf16/f32 pool -> wire: gather the blocks and quantize them to
    the int8+scales wire format (ops/quant.py — the EQuARX recipe the
    wire collectives use, ~4x fewer bytes than bf16). Lossy at the
    quantizer's amax/254 per-block bound; int8 pools take the lossless
    path above."""
    out = []
    for layer in caches:
        entry = {}
        for kv in ("k", "v"):
            q, s = quant.quantize_kv_block(quant.split_heads(
                jnp.take(layer[kv], idx, axis=0), heads))
            entry[kv] = q
            entry[f"{kv}_scale"] = s
        out.append(entry)
    return out


def _scatter_blocks_quantized(caches, idx, payload):
    """Wire -> int8 pool: write int8 blocks + scale rows verbatim at
    the freshly allocated (ref == 1) indices."""
    out = []
    for layer, pay in zip(caches, payload):
        new = dict(layer)
        for kv in ("k", "v"):
            new[kv] = layer[kv].at[idx].set(
                quant.merge_heads(pay[kv]).astype(layer[kv].dtype))
            sc = f"{kv}_scale"
            new[sc] = layer[sc].at[idx].set(pay[sc].astype(layer[sc].dtype))
        out.append(new)
    return out


def _scatter_blocks_dequant(caches, idx, payload):
    """Wire -> bf16/f32 pool: dequantize the int8 blocks to the pool
    dtype and write them at the freshly allocated indices."""
    out = []
    for layer, pay in zip(caches, payload):
        new = dict(layer)
        for kv in ("k", "v"):
            blk = quant.dequantize_kv_block(
                pay[kv], pay[f"{kv}_scale"], layer[kv].dtype)
            new[kv] = layer[kv].at[idx].set(quant.merge_heads(blk))
        out.append(new)
    return out


_gather_blocks_quantized_jit = jax.jit(_gather_blocks_quantized)
_gather_quantize_blocks_jit = jax.jit(_gather_quantize_blocks,
                                      static_argnums=(2,))
_scatter_blocks_quantized_jit = jax.jit(_scatter_blocks_quantized,
                                        donate_argnums=(0,))
_scatter_blocks_dequant_jit = jax.jit(_scatter_blocks_dequant,
                                      donate_argnums=(0,))


class PagedSlotPool:
    """Block-paged KV pool: ref-counted blocks + per-slot block tables.

    Device state: ``caches``, one dict of leaves a layer as the model
    declared them (``model.cache_leaves``: a group and the leaves of one
    block), each leaf ``[blocks of the layer's group, ...]``: K/V rows
    ``[N, block_size, KVH*D]`` (lane-dense: a position's heads side by
    side, head ``h`` in lanes ``h*D .. (h+1)*D``), or a latent row. And,
    uploaded per dispatch from the host mirrors (:meth:`device_tables`),
    one table a group: ``tables_host`` (``[capacity, blocks_per_slot]``
    int32; entry ``[s, i]`` is the block of the GROWING group holding
    slot ``s``'s positions ``[i*bs, (i+1)*bs)``, or 0/scratch when
    unbound) and, where the model has window layers,
    ``window_tables_host`` (``[capacity, window_entries]``: the slot's
    ring, every entry bound from :meth:`alloc` to :meth:`free`) and,
    where it has state layers, ``state_tables_host`` (``[capacity, 1]``:
    the slot's one state entry, bound likewise). Host
    state: a free list and ref counts a group, per-slot bound counts,
    and the prefix trie. ``num_blocks``, ``blocks_used``,
    ``bytes_resident`` and ``available_blocks`` are the growing group's
    (admission runs out of those); the ring's are ``window_blocks_used``
    and ``window_bytes_resident``, the state group's
    ``state_slots_bound`` and ``state_bytes_resident``.

    With ``quantized=True`` (``ServeConfig.kv_dtype="int8"``) the K/V
    pools store int8 and each layer carries ``k_scale``/``v_scale``
    fp32 buffers shaped ``[num_blocks, H]`` — one absmax scale per
    (block, head), written by the in-program block-granularity
    quantizer (models/gpt2.py) and consumed by the flash-decode
    kernel's in-loop dequant. Because scales are block-indexed leaves
    of the SAME caches pytree, every lifecycle move is shared: COW
    copies a block's scale row with it, freeing/rebinding a block
    implicitly retires its stale scale (the next occupant's first
    write recomputes it — stale positions are zeroed before the
    block's absmax is taken, so a previous occupant can never inflate
    the new scale), and the stale-KV poisoning regression covers scale
    rows too.

    Invariants (the chaos tests' leak check asserts them):

    - block 0 is scratch: never allocated, never ref-counted;
    - a block is written only while its ref count is exactly 1
      (:meth:`prepare_write` COWs shared blocks first);
    - every non-free block's ref count equals (slots binding it) +
      (1 if a trie node caches it);
    - freeing the last reference returns the block to the free list.
    """

    def __init__(self, model, capacity: int, max_len: int,
                 dtype=jnp.bfloat16, *, block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 prefix_cache: bool = True, eviction: str = "lru",
                 quantized: bool = False, host_blocks: int = 0):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if max_len < 1:
            raise ValueError(f"max_len must be >= 1, got {max_len}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if eviction not in ("lru", "none"):
            raise ValueError(
                f"eviction must be 'lru' or 'none', got {eviction!r}")
        if host_blocks < 0:
            raise ValueError(
                f"host_blocks must be >= 0, got {host_blocks}")
        if host_blocks and not quantized:
            # The host tier stores the pool's native block bytes, and
            # only int8 blocks ARE the wire format (lossless round
            # trip). A bf16 tier would silently serve quantize-dequant
            # blocks that differ from a fresh prefill — refuse rather
            # than make hit-vs-miss results diverge.
            raise ValueError(
                "host_blocks requires a quantized (int8) pool — the "
                "demoted payload is the int8+scales block verbatim")
        if host_blocks and not prefix_cache:
            raise ValueError(
                "host_blocks requires prefix_cache (demotion feeds off "
                "trie eviction; without the trie the tier is inert)")
        self.capacity = capacity
        self.max_len = max_len
        self.dtype = dtype
        self.block_size = block_size
        # Table width: every slot must be able to reach max_len.
        self.blocks_per_slot = math.ceil(max_len / block_size)
        if num_blocks is None:
            # Every slot can reach max_len by default (+1 for scratch):
            # then slots, not blocks, are what admission runs out of.
            num_blocks = 1 + capacity * self.blocks_per_slot
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (block 0 is scratch), got "
                f"{num_blocks}")
        self.num_blocks = num_blocks
        self.prefix_cache_enabled = prefix_cache
        self.eviction = eviction
        self.quantized = quantized
        # The model DECLARES, layer by layer, the group its cache lives in
        # and the leaves of one block (name -> trailing shape and dtype);
        # the pool allocates ``[blocks of the group, ...]`` of each and
        # does its byte accounting from the same declaration. GPT-2:
        # lane-dense ``k`` / ``v`` rows ``[N, bs, H*D]`` and, with
        # ``ServeConfig.kv_dtype="int8"``, int8 blocks plus one fp32
        # absmax scale per (block, head): the ``[num_blocks, H]`` scale
        # buffers ride IN the caches pytree, so everything that moves a
        # block (program donation, COW copy, checkpoint of the tree
        # structure) moves its scale row with it by construction. A
        # latent-attention model: one ``latent`` ``[N, bs, width]`` leaf,
        # no head axis. Zero-init: q = 0 with scale 0 dequantizes to
        # exact zeros, same as a float pool's zero init. A layer with a
        # ``window`` lives in the ring group: its leaves hold
        # ``1 + capacity * window_entries`` blocks whatever ``max_len``
        # is. Block lifecycle and ref counts are one implementation over
        # any leaf set and either group; COW and the trie are the growing
        # group's.
        layers = model.cache_leaves(block_size, dtype, quantized)
        windows = {w for _, w, _ in layers if w is not None}
        if len(windows) > 1:
            raise ValueError(
                f"one window group a pool: layers declare windows "
                f"{sorted(windows)}")
        self.window: Optional[int] = windows.pop() if windows else None
        self.window_entries = (ring_entries(self.window, block_size)
                               if self.window else 0)
        if self.window and prefix_cache:
            raise ValueError(
                "prefix_cache with window layers: a ring overwrites the "
                "blocks a trie hit would have to re-bind (a hit would "
                "need the window layers' last tokens as a snapshot)")
        self.layer_groups: Tuple[str, ...] = tuple(g for g, _, _ in layers)
        unknown = set(self.layer_groups) - {"global", "window", "state"}
        if unknown:
            raise ValueError(f"unknown cache groups {sorted(unknown)}")
        # A state layer's leaves are per slot: one entry each.
        self.state_entries = int("state" in self.layer_groups)
        if self.state_entries and prefix_cache:
            raise ValueError(
                "prefix_cache with state layers: a trie hit at a block "
                "boundary would need the recurrent state AT that boundary "
                "as a snapshot, and the pool keeps only the latest")
        ring_blocks = 1 + capacity * self.window_entries
        state_blocks = 1 + capacity * self.state_entries
        entries = {"global": num_blocks, "window": ring_blocks,
                   "state": state_blocks}
        self.caches = [
            {name: jnp.zeros((entries[g],) + tuple(shape), dt)
             for name, (shape, dt) in leaves.items()}
            for g, _, leaves in layers]

        def block_bytes(group: str) -> int:
            return sum(math.prod(shape) * jnp.dtype(dt).itemsize
                       for g, _, leaves in layers if g == group
                       for shape, dt in leaves.values())

        # Per-block device footprint (every leaf, all the group's
        # layers): the serve.kv.bytes_resident gauge's unit and the
        # equal-memory bench's conversion rate between int8 and bf16
        # block budgets.
        self.bytes_per_block = block_bytes("global")
        self.window_bytes_per_block = block_bytes("window")
        self.state_bytes_per_entry = block_bytes("state")
        # Migration, peer pulls and the host tier speak one wire format:
        # int8 K/V blocks as per-head tiles ``[n, H, bs, D]`` +
        # per-(block, head) scales ``[n, H]``. A pool of other leaves,
        # or one with a ring (whose blocks no table row spells in
        # order) or a state (which is no block of tokens), has none.
        self.kv_wire = set(self.layer_groups) == {"global"} and all(
            {"k", "v"} <= set(leaves) for _, _, leaves in layers)
        self.wire_block_shape: Optional[Tuple[int, int, int]] = None
        if self.kv_wire:
            heads = model.cfg.num_heads
            self.wire_block_shape = (
                heads, block_size, layers[0][2]["k"][0][-1] // heads)
        self.tables_host = np.zeros((capacity, self.blocks_per_slot),
                                    np.int32)
        self._free_slots: List[int] = list(range(capacity - 1, -1, -1))
        # Block 0 reserved as scratch (pad-write sink for non-emitting
        # rows), a LIFO free list over the rest. ``_free_blocks`` and
        # ``_refs`` ARE the growing group's books (the same list and
        # array), under the names the code below has always used.
        self._books = _BlockBooks(num_blocks)
        self._free_blocks: List[int] = self._books.free
        self._refs = self._books.refs
        self._bound = np.zeros((capacity,), np.int32)   # per-slot entries
        # The window group's ring: every entry of a taken slot is bound.
        self._ring_books = _BlockBooks(ring_blocks)
        self.window_tables_host = np.zeros(
            (capacity, self.window_entries), np.int32)
        # The state group: a taken slot's one entry.
        self._state_books = _BlockBooks(state_blocks)
        self.state_tables_host = np.zeros(
            (capacity, self.state_entries), np.int32)
        self.trie = PrefixTrie(block_size)
        self.cow_copies = 0
        self.prefix_hits = 0
        # Host tier (0 = disabled): demoted blocks' int8+scales wire
        # payloads, LRU-ordered (oldest first), keyed by the FULL
        # prompt-prefix token path that block's K/V encodes. One entry
        # is one block: per-layer {"k","v","k_scale","v_scale"} host
        # arrays shaped [1, H, bs, D] / [1, H] (the wire's per-head
        # tiles, not the pool's rows).
        self.host_blocks = host_blocks
        self._host_tier: "collections.OrderedDict[Tuple[int, ...], list]" \
            = collections.OrderedDict()
        self._host_bytes = 0
        self.demotions = 0
        self.promotions = 0
        self.promote_failures = 0
        # Fleet tier tags (PR 17): blocks whose content arrived over a
        # PEER pull (vs local prefill / migration) — the first trie hit
        # on such a block is a fleet "peer" hit, after which the block
        # is indistinguishable from local device cache and is counted
        # as such. The per-tier hit ledger feeds the
        # serve.kv.fleet_hits_* counters; one request counts at most
        # once per tier it touched.
        self._peer_blocks: set = set()
        self.fleet_hits = {"device": 0, "host": 0, "peer": 0}
        # Mirror pool: a second pool shadowing this one's slot lifecycle
        # (the speculative engine's DRAFT KV pool). alloc/free mirror by
        # slot INDEX, so the draft's cache for request R always lives at
        # the target's slot and freeing the target slot can never leak
        # the draft's blocks; block bookkeeping stays per-pool (the
        # draft binds its own blocks lazily, sized by the draft model's
        # geometry). leak_check recurses into it, so the
        # chaos oracles cover both pools in one call.
        self.mirror = None

    # ------------------------------------------------------ slot layer
    def alloc(self) -> Optional[int]:
        """-> a free slot index, or None when every slot is occupied.
        Blocks are bound separately (:meth:`bind_for_prompt` /
        :meth:`prepare_write`) — a fresh slot holds none."""
        slot = self._free_slots.pop() if self._free_slots else None
        if slot is not None:
            self._bind_slot_entries(slot)
            if self.mirror is not None:
                self.mirror.claim(slot)
        return slot

    def _bind_slot_entries(self, slot: int) -> None:
        """A taken slot holds its whole ring and its state entry until
        :meth:`free` (each group reserves a slot's entries, so this
        never runs dry)."""
        for i in range(self.window_entries):
            self.window_tables_host[slot, i] = self._ring_books.take()
        for i in range(self.state_entries):
            self.state_tables_host[slot, i] = self._state_books.take()

    def claim(self, slot: int) -> None:
        """Take a SPECIFIC free slot (the mirror path: the leader pool
        chose the index). Raises when the slot is not free: lifecycle
        drift between the pools must surface, not corrupt."""
        self._free_slots.remove(slot)
        self._bind_slot_entries(slot)

    def free(self, slot: int) -> None:
        """Release the slot and DROP ITS BLOCK REFERENCES in the same
        call (the same-iteration contract the chaos suites pin): blocks
        nobody else references return to the free list, the table row
        resets to scratch so a stale dispatch mask can never write into
        a rebound block. A mirror pool (the draft cache) frees the same
        slot — and its own blocks — in the same call."""
        if not 0 <= slot < self.capacity:
            raise ValueError(f"slot {slot} out of range [0, {self.capacity})")
        if slot in self._free_slots:
            raise ValueError(f"slot {slot} is already free (double free)")
        self.release_blocks(slot)
        for books, table in ((self._ring_books, self.window_tables_host),
                             (self._state_books, self.state_tables_host)):
            for b in table[slot]:
                books.release(int(b))
            table[slot, :] = 0
        self._free_slots.append(slot)
        if self.mirror is not None:
            self.mirror.free(slot)

    def release_blocks(self, slot: int) -> None:
        """Drop the slot's references in the growing group (without
        freeing the slot; its ring stays bound):
        the table row resets to scratch and blocks nobody else holds
        return to the free list. Used by :meth:`free` and by the
        engine's cold-prefill fallback when a prefix hit pinned the
        very blocks its own copy-on-write then needed."""
        for i in range(int(self._bound[slot])):
            self._release(int(self.tables_host[slot, i]))
        self.tables_host[slot, :] = 0
        self._bound[slot] = 0

    @property
    def num_free(self) -> int:
        return len(self._free_slots)

    @property
    def num_active(self) -> int:
        return self.capacity - len(self._free_slots)

    @property
    def occupancy(self) -> float:
        return self.num_active / self.capacity

    # ----------------------------------------------------- block layer
    @property
    def blocks_used(self) -> int:
        """Non-free, non-scratch blocks (slot-bound + trie-cached) —
        the ``serve.kv.blocks_used`` gauge value."""
        return self.num_blocks - 1 - len(self._free_blocks)

    @property
    def bytes_resident(self) -> int:
        """Device bytes the resident blocks hold (K/V data + scale rows
        when quantized) — the ``serve.kv.bytes_resident`` gauge. The
        capacity lever in one number: at the same byte budget an int8
        pool holds ~2x the blocks of a bf16 pool (scale overhead is
        ``4 / (block_size * D)`` per element)."""
        return self.blocks_used * self.bytes_per_block

    @property
    def window_blocks_used(self) -> int:
        """Ring blocks the taken slots hold (``window_entries`` each):
        the ``serve.kv.window_blocks_used`` gauge value."""
        return self._ring_books.used

    @property
    def window_bytes_resident(self) -> int:
        """Device bytes of the bound ring blocks, all window layers."""
        return self.window_blocks_used * self.window_bytes_per_block

    @property
    def state_slots_bound(self) -> int:
        """State entries the taken slots hold (one each): the
        ``serve.state.slots_bound`` gauge value."""
        return self._state_books.used

    @property
    def state_bytes_resident(self) -> int:
        """Device bytes of the bound state entries, all state layers."""
        return self.state_slots_bound * self.state_bytes_per_entry

    def device_tables(self) -> Dict[str, jax.Array]:
        """The host tables as a dispatch uploads them: one a group,
        keyed as ``layer_groups`` names them."""
        tables = {"global": jnp.asarray(self.tables_host)}
        if self.window:
            tables["window"] = jnp.asarray(self.window_tables_host)
        if self.state_entries:
            tables["state"] = jnp.asarray(self.state_tables_host)
        return tables

    @property
    def trie_only_blocks(self) -> int:
        """Blocks held ONLY by the prefix cache (ref == 1 via a trie
        node) — the evictable count."""
        return sum(1 for b in self.trie.blocks if self._refs[b] == 1)

    def available_blocks(self) -> int:
        """Free blocks plus what eviction could reclaim — the
        scheduler's admission budget."""
        n = len(self._free_blocks)
        if self.eviction == "lru":
            n += self.trie_only_blocks
        return n

    def blocks_for_span(self, end: int) -> int:
        """Blocks covering positions ``[0, end)``."""
        return math.ceil(min(end, self.max_len) / self.block_size)

    @property
    def max_request_blocks(self) -> int:
        """The most blocks one request could ever bind."""
        return min(self.blocks_per_slot, self.num_blocks - 1)

    def _alloc_block(self, slot: Optional[int]) -> int:
        """Pop a free block (evicting LRU trie-only cache blocks if the
        list is dry). ``serve.kv.bind`` is the chaos point: an injected
        error surfaces exactly like genuine exhaustion — typed
        backpressure, request-scoped, never a crash."""
        faults.point("serve.kv.bind")
        if not self._free_blocks and self.eviction == "lru":
            # Only evict entries whose release actually frees a block
            # (ref == 1, trie-only): evicting a leaf a live request
            # still binds would destroy cache value AND free nothing —
            # exhaustion must only be raised once every reclaimable
            # block has genuinely been reclaimed (the capacity
            # available_blocks() promised admission). With a host tier
            # configured the victim's payload is demoted to host RAM
            # first instead of being discarded.
            self.trie.evict(1, self._release,
                            only=lambda b: self._refs[b] == 1,
                            on_evict=(self._demote if self.host_blocks
                                      else None))
        if not self._free_blocks:
            raise KVBlocksExhausted(
                f"no free KV blocks ({self.blocks_used}/"
                f"{self.num_blocks - 1} in use, "
                f"{len(self.trie)} cached)", slot=slot)
        return self._books.take()

    def _release(self, block: int) -> None:
        if self._books.release(block):
            # A freed block's peer tag dies with it: the index will be
            # rebound to unrelated content, which must count as local.
            self._peer_blocks.discard(block)

    # ------------------------------------------------------- host tier
    @property
    def host_blocks_used(self) -> int:
        """Demoted blocks resident in the host tier — the
        ``serve.kv.host_blocks_used`` gauge value."""
        return len(self._host_tier)

    @property
    def host_bytes_resident(self) -> int:
        """Host RAM the demoted payloads hold (int8 data + fp32 scale
        rows, all layers) — the ``serve.kv.host_bytes_resident``
        gauge."""
        return self._host_bytes

    @staticmethod
    def _entry_bytes(entry: List[Dict[str, np.ndarray]]) -> int:
        return sum(a.nbytes for layer in entry for a in layer.values())

    def _host_put(self, key: Tuple[int, ...], entry: list) -> None:
        """Insert one payload at the tier's MRU end with the byte books
        adjusted and the LRU budget cap re-applied — the ONE place the
        host-tier accounting invariant (that :meth:`leak_check`'s host
        column audits) is maintained; both demotion and the failed-
        promote restore route through here."""
        old = self._host_tier.pop(key, None)
        if old is not None:
            self._host_bytes -= self._entry_bytes(old)
        self._host_tier[key] = entry
        self._host_bytes += self._entry_bytes(entry)
        # Host LRU: the budget is a hard cap — oldest entries drop
        # (for good; there is no colder tier below host RAM).
        while len(self._host_tier) > self.host_blocks:
            _, dropped = self._host_tier.popitem(last=False)
            self._host_bytes -= self._entry_bytes(dropped)

    def _demote(self, path_tokens: Tuple[int, ...], block: int) -> None:
        """Trie-eviction hook: capture ``block``'s int8 payload +
        scales into the host tier before the block returns to the free
        list. The gather is the migration export op on one index; the
        device→host copies are started async and collected immediately
        (the eviction path is about to rebind this block, so the bytes
        must land before the pool's next write — the copy overlaps the
        per-leaf ``np.asarray`` walk, not the decode hot path)."""
        idx = jnp.asarray(np.asarray([block], np.int32))
        layers = _gather_blocks_quantized_jit(self.caches, idx)
        for layer in layers:
            for arr in layer.values():
                copy_async = getattr(arr, "copy_to_host_async", None)
                if copy_async is not None:
                    copy_async()
        entry = [{k: np.asarray(v) for k, v in layer.items()}
                 for layer in layers]
        self._host_put(path_tokens, entry)
        self.demotions += 1
        obs.counter("serve.kv.demotions_total").inc()

    def _promote(self, slot: int, tokens: List[int],
                 start_blocks: int) -> int:
        """Extend a device trie match through the host tier: promote
        the longest run of consecutively host-cached blocks past the
        ``start_blocks`` device-matched ones back onto the device —
        fresh ref == 1 allocations, the wire payload scattered in by
        the migration install op, the blocks re-indexed in the trie
        and referenced by ``slot``. The scatter is DISPATCHED before
        any bookkeeping (async host→device; the prefill chunks that
        follow queue behind it on the device stream — the engine's
        copy_to_host_async-then-bookkeep idiom, reversed). Degrades to
        a cold prefill — typed, counted, nothing leaked — when the
        pool cannot hold the span or the ``serve.kv.promote`` fault
        point fires. -> blocks promoted."""
        bs = self.block_size
        # Never promote the block holding position n-1: the final
        # prompt token always re-runs (its logits seed decoding), so
        # that block would COPY-ON-WRITE immediately — one allocation
        # MORE than the cold footprint the scheduler's admission
        # budget promised (on a pool at the admission edge the COW
        # would then exhaust, throwing the whole promote away via the
        # engine's cold fallback). Capped at (n-1)//bs, a promote
        # allocates exactly the blocks a cold prefill of the same span
        # would have bound. Device-trie hits keep matching the final
        # block — they take references (0 allocations), so their COW
        # stays within budget.
        limit = min((len(tokens) - 1) // bs, self.blocks_per_slot)
        keys: List[Tuple[int, ...]] = []
        entries: List[list] = []
        bi = start_blocks
        while bi < limit:
            key = tuple(tokens[:(bi + 1) * bs])
            entry = self._host_tier.get(key)
            if entry is None:
                break
            keys.append(key)
            entries.append(entry)
            bi += 1
        if not entries:
            return 0
        with obs.span("serve.kv.promote_s", blocks=len(entries)):
            try:
                faults.point("serve.kv.promote")
            except faults.InjectedFault:
                # The pinned degrade drill: the request simply
                # prefills cold; the host entries stay resident for
                # the next hit.
                self.promote_failures += 1
                return 0
            # Exclusive move: pop the entries FIRST, so a demotion our
            # own allocations trigger (eviction under pressure) can
            # never race the host-LRU into dropping what we're reading.
            for key, entry in zip(keys, entries):
                self._host_tier.pop(key, None)
                self._host_bytes -= self._entry_bytes(entry)
            blocks: List[int] = []
            try:
                for _ in entries:
                    blocks.append(self._alloc_block(slot))
            except (KVBlocksExhausted, faults.InjectedFault):
                # Typed degrade: release what we allocated, put the
                # entries back (MRU — they were just wanted), prefill
                # cold. Admission budgeted for exactly this no-hit
                # footprint, so nothing downstream is surprised. The
                # allocs that DID succeed may each have demoted a
                # third-party block into the tier, so the restore must
                # re-apply the LRU budget cap — _host_put does.
                for b in blocks:
                    self._release(b)
                for key, entry in zip(keys, entries):
                    self._host_put(key, entry)
                self.promote_failures += 1
                return 0
            # Async host->device: dispatch the uploads + scatters NOW;
            # every line after this is host bookkeeping the copies
            # overlap. Later device work (COW, prefill chunks) takes
            # self.caches as input, so XLA's dataflow ordering — not a
            # host sync — guarantees the promoted bytes land first.
            # The install jit keys on the index SHAPE, so the span is
            # scattered in POWER-OF-TWO runs (the prefill-bucket idiom
            # one level down): an m-block promote costs popcount(m)
            # dispatches against at most log2(blocks_per_slot) compiled
            # maintenance programs, all warmable off the clock
            # (:meth:`warm_host_tier_programs`) — never one program per
            # distinct m compiling inside a measured TTFT window.
            off = 0
            while off < len(blocks):
                run = 1
                while run * 2 <= len(blocks) - off:
                    run *= 2
                idx = jnp.asarray(
                    np.asarray(blocks[off:off + run], np.int32))
                chunk = entries[off:off + run]
                payload = [
                    {k: jnp.asarray(np.concatenate(
                        [e[li][k] for e in chunk], axis=0))
                     for k in chunk[0][li]}
                    for li in range(len(chunk[0]))]
                self.caches = _scatter_blocks_quantized_jit(
                    self.caches, idx, payload)
                off += run

            def take_ref(block: int) -> None:
                self._refs[block] += 1

            # Re-index under the trie (existing device-prefix nodes are
            # kept — insert only takes refs on the NEW nodes), then
            # bind the promoted span to the slot, then drop our
            # allocation refs: each promoted block ends at ref 2 (trie
            # + slot), exactly like a device prefix hit.
            path = ([int(b) for b in self.tables_host[slot, :start_blocks]]
                    + blocks)
            self.trie.insert(tokens[:bi * bs], path, take_ref)
            for i, b in enumerate(blocks):
                self._refs[b] += 1
                self.tables_host[slot, start_blocks + i] = b
            self._bound[slot] = start_blocks + len(blocks)
            for b in blocks:
                self._release(b)
            self.promotions += len(blocks)
            obs.counter("serve.kv.promotions_total").inc(len(blocks))
        return len(blocks)

    def clear_host_tier(self) -> int:
        """Drop every demoted payload (knob flips / tests / operator
        relief valve). -> entries dropped."""
        n = len(self._host_tier)
        self._host_tier.clear()
        self._host_bytes = 0
        return n

    def warm_host_tier_programs(self) -> None:
        """Compile the demote/promote maintenance programs — the
        one-block gather plus every power-of-two scatter width up to
        ``blocks_per_slot`` (promotion batches in power-of-two runs) —
        off the measured clock, via identity rewrites of the scratch
        block (never ref-counted, content is pad garbage by contract —
        writing it with its own bytes, even ``run`` times over, changes
        nothing). Benchmarks call this during warmup so the first real
        demotion/promotion never pays a compile inside a measured TTFT
        window; skipping it costs exactly those spikes, nothing else."""
        if not (self.host_blocks and self.quantized):
            return
        one = jnp.asarray(np.zeros((1,), np.int32))
        layers = _gather_blocks_quantized_jit(self.caches, one)
        entry = [{k: np.asarray(v) for k, v in layer.items()}
                 for layer in layers]
        run = 1
        while run <= self.blocks_per_slot:
            idx = jnp.asarray(np.zeros((run,), np.int32))
            payload = [
                {k: jnp.asarray(np.repeat(v, run, axis=0))
                 for k, v in layer.items()}
                for layer in entry]
            self.caches = _scatter_blocks_quantized_jit(
                self.caches, idx, payload)
            run *= 2

    # -------------------------------------------------- prompt binding
    def bind_for_prompt(self, slot: int, tokens: Sequence[int]) -> int:
        """Admission-time binding: match the prompt's full-block prefix
        against the trie and take REFERENCES on the cached blocks
        instead of re-prefilling them; with a host tier configured,
        extend the match through host-demoted blocks (promoted back as
        fresh allocations — see :meth:`_promote`). -> ``shared_len``,
        the number of leading positions whose K/V the slot now holds
        (block-aligned, capped at ``len(tokens) - 1`` so the final
        prompt token is always re-run — its logits seed decoding). The
        cap can land the first write inside the last shared block;
        :meth:`prepare_write` COWs it then."""
        if self._bound[slot]:
            raise ValueError(f"slot {slot} already holds blocks")
        n = len(tokens)
        toks = [int(t) for t in tokens]
        shared_blocks: List[int] = []
        if self.prefix_cache_enabled:
            shared_blocks = self.trie.match(toks)
        nshared = len(shared_blocks)
        if shared_blocks:
            for i, b in enumerate(shared_blocks):
                self._refs[b] += 1
                self.tables_host[slot, i] = b
            self._bound[slot] = nshared
        promoted = 0
        if self.host_blocks and self.prefix_cache_enabled:
            promoted = self._promote(slot, toks, nshared)
            nshared += promoted
        # Fleet three-tier hit accounting (PR 17): classify where this
        # request's reused blocks came from. Peer-pulled blocks count
        # as "peer" on their FIRST reuse (then revert to plain device
        # cache); host promotions count as "host"; everything else the
        # trie matched is "device". One bump per tier per request, one
        # bump of the roll-up total per request-with-any-hit.
        if nshared:
            tiers = []
            pulled = self._peer_blocks.intersection(shared_blocks)
            if pulled:
                self._peer_blocks.difference_update(pulled)
                tiers.append("peer")
            if len(pulled) < len(shared_blocks):
                tiers.append("device")
            if promoted:
                tiers.append("host")
            for t in tiers:
                self.fleet_hits[t] += 1
                obs.counter(f"serve.kv.fleet_hits_{t}_total").inc()
            obs.counter("serve.kv.fleet_hits_total").inc()
        return min(nshared * self.block_size, n - 1)

    def count_prefix_hit(self) -> None:
        """Account one MATERIALIZED prefix hit. Called by the engine
        after the hit's write binding succeeded — not inside
        :meth:`bind_for_prompt` — so a tight-pool hit that had to fall
        back to a cold prefill never inflates the cache-win metrics."""
        self.prefix_hits += 1
        obs.counter("serve.kv.prefix_hits_total").inc()

    def register_prefix(self, slot: int, tokens: Sequence[int]) -> int:
        """Post-prefill: index the prompt's full blocks (now holding
        exactly those tokens' K/V) in the trie, which takes its own
        reference per newly cached block — the cache outlives the
        donor. -> nodes inserted."""
        if not self.prefix_cache_enabled:
            return 0
        nfull = len(tokens) // self.block_size
        if nfull == 0 or self._bound[slot] < nfull:
            return 0

        def take_ref(block: int) -> None:
            self._refs[block] += 1

        return self.trie.insert(
            list(tokens)[:nfull * self.block_size],
            [int(b) for b in self.tables_host[slot, :nfull]], take_ref)

    # ------------------------------------------------------ write path
    def prepare_write(self, slot: int, start: int, end: int) -> int:
        """Make positions ``[start, end)`` of ``slot`` writable before a
        dispatch that will write them: bind fresh blocks past the bound
        frontier, and copy-on-write any block in the span whose ref
        count exceeds 1 (shared prefix, or a donor's block the cache /
        another request references). Raises :class:`KVBlocksExhausted`
        (typed backpressure) when no block can be found. -> the blocks
        it allocated (0: the span was bound and exclusively owned)."""
        bs = self.block_size
        taken = 0
        end = min(end, self.blocks_per_slot * bs)
        first = min(start // bs, int(self._bound[slot]))
        last = math.ceil(end / bs)
        for bi in range(first, last):
            if bi < self._bound[slot]:
                b = int(self.tables_host[slot, bi])
                if self._refs[b] > 1:
                    nb = self._alloc_block(slot)
                    grown = [i for i, g in enumerate(self.layer_groups)
                             if g == "global"]
                    copied = _copy_block_jit(
                        [self.caches[i] for i in grown],
                        np.int32(b), np.int32(nb))
                    for i, layer in zip(grown, copied):
                        self.caches[i] = layer
                    self.tables_host[slot, bi] = nb
                    self._release(b)
                    self.cow_copies += 1
                    taken += 1
                    obs.counter("serve.kv.cow_copies_total").inc()
            else:
                if bi != self._bound[slot]:
                    raise AssertionError(
                        f"non-contiguous bind: slot {slot} bound "
                        f"{int(self._bound[slot])} blocks, write wants "
                        f"block {bi}")
                self.tables_host[slot, bi] = self._alloc_block(slot)
                self._bound[slot] = bi + 1
                taken += 1
        return taken

    # ------------------------------------------------------- migration
    def export_block_payload(self, slot: int, nblocks: int
                             ) -> Tuple[List[Dict[str, np.ndarray]], int]:
        """Export the first ``nblocks`` bound blocks of ``slot`` in the
        int8+scales wire layout: -> (per-layer ``{"k", "k_scale", "v",
        "v_scale"}`` host arrays, total payload bytes). int8 pools
        export their blocks verbatim (a migrated block is
        bit-identical on the destination); bf16/f32 pools quantize to
        the wire format on device first (lossy at the quantizer's
        per-block amax/254 bound — the same bound
        ``serve.kv.quant_error`` samples). Export is read-only: the
        source's refs are untouched — release is the ACK's job
        (two-phase handoff, serve/migrate.py). On a head-sharded pool
        (serve/sharded) the host conversion IS the gather: the wire
        payload always carries full heads, whatever mesh the source
        ran (gather-on-export)."""
        self._require_kv_wire()
        if not 1 <= nblocks <= int(self._bound[slot]):
            raise ValueError(
                f"cannot export {nblocks} block(s) from slot {slot}: "
                f"{int(self._bound[slot])} bound")
        idx = jnp.asarray(self.tables_host[slot, :nblocks].copy())
        if self.quantized:
            layers = _gather_blocks_quantized_jit(self.caches, idx)
        else:
            layers = _gather_quantize_blocks_jit(
                self.caches, idx, self.wire_block_shape[0])
        host = [{k: np.asarray(v) for k, v in layer.items()}
                for layer in layers]
        nbytes = sum(a.nbytes for layer in host for a in layer.values())
        return host, nbytes

    def _require_kv_wire(self) -> None:
        if not self.kv_wire:
            raise ValueError(
                f"this pool's cache leaves ({sorted(self.caches[0])}) have "
                f"no migration wire format: block export/install moves "
                f"int8 K/V blocks with per-head scales")

    # ------------------------------------------------------ fleet cache
    def digest_entries(self):
        """Yield ``(path_tokens, tier)`` for every cached prefix this
        pool could serve — device trie paths first (hottest first, by
        LRU tick), then host-tier keys (MRU first) — the recency order
        :func:`fleetcache.build_digest` truncates against. A bounded
        host walk (no device ops); callers hold the scheduler lock."""
        if self.prefix_cache_enabled:
            for node in sorted(self.trie._nodes,
                               key=lambda n: n.tick, reverse=True):
                yield self.trie._path_tokens(node), "device"
        for key in reversed(self._host_tier):
            yield key, "host"

    def export_prefix_payload(self, tokens: Sequence[int]
                              ) -> Tuple[List[int],
                                         List[Dict[str, np.ndarray]], int]:
        """Peer-pull export (PR 17): the longest cached full-block
        prefix of ``tokens`` this pool holds — device trie match,
        extended through consecutively host-cached blocks — gathered
        into the int8+scales wire layout WITHOUT touching any slot.
        -> ``(covered_tokens, per-layer wire arrays, payload bytes)``;
        zero coverage returns ``([], [], 0)`` (a legal empty wire —
        digests are advisory, a stale one costs one wasted probe).
        Read-only like :meth:`export_block_payload`: refs, trie and
        host tier are untouched; the source gives up nothing."""
        self._require_kv_wire()
        toks = [int(t) for t in tokens]
        bs = self.block_size
        blocks: List[int] = []
        if self.prefix_cache_enabled:
            blocks = self.trie.match(toks)
        host_entries: List[list] = []
        bi = len(blocks)
        while (bi + 1) * bs <= len(toks):
            entry = self._host_tier.get(tuple(toks[:(bi + 1) * bs]))
            if entry is None:
                break
            host_entries.append(entry)
            bi += 1
        nblocks = len(blocks) + len(host_entries)
        if nblocks == 0:
            return [], [], 0
        host: List[Dict[str, np.ndarray]] = []
        if blocks:
            idx = jnp.asarray(np.asarray(blocks, np.int32))
            if self.quantized:
                layers = _gather_blocks_quantized_jit(self.caches, idx)
            else:
                layers = _gather_quantize_blocks_jit(
                    self.caches, idx, self.wire_block_shape[0])
            host = [{k: np.asarray(v) for k, v in layer.items()}
                    for layer in layers]
        if host_entries:
            if host:
                host = [{k: np.concatenate(
                            [layer[k]] + [e[li][k] for e in host_entries],
                            axis=0)
                         for k in layer}
                        for li, layer in enumerate(host)]
            else:
                host = [{k: np.concatenate(
                            [e[li][k] for e in host_entries], axis=0)
                         for k in host_entries[0][li]}
                        for li in range(len(host_entries[0]))]
        nbytes = sum(a.nbytes for layer in host for a in layer.values())
        return toks[:nblocks * bs], host, nbytes

    def install_block_payload(self, tokens: Sequence[int],
                              layers: List[Dict[str, np.ndarray]],
                              origin: str = "migrate") -> int:
        """Install a migrated block payload into the PREFIX CACHE:
        allocate fresh blocks (ref == 1 — the write invariant holds by
        construction, these indices are owned by nobody), scatter the
        wire data in (dequantized to the pool dtype, or verbatim into
        an int8 pool), and index the blocks in the trie keyed on
        ``tokens``' full-block prefix. The installing request then
        takes prefix-cache REFERENCES through the ordinary
        ``bind_for_prompt`` path — migration reuses the exact reuse
        machinery prefix hits already proved out. -> blocks newly
        referenced by the trie (0 when the prefix was already cached,
        the payload is empty, or the prefix cache is disabled — the
        request simply prefills cold). Raises
        :class:`KVBlocksExhausted` (typed, retryable — nothing is
        leaked) when the pool cannot hold the span, and ``ValueError``
        on a payload whose geometry does not match this pool.

        ``origin="peer"`` (PR 17 fleet pull) tags the newly indexed
        blocks so their first reuse is counted as a fleet "peer" hit;
        ``"migrate"`` (the PR 11 two-phase handoff) leaves the tier
        accounting untouched."""
        self._require_kv_wire()
        nblocks = int(layers[0]["k"].shape[0]) if layers else 0
        if nblocks == 0 or not self.prefix_cache_enabled:
            return 0
        bs = self.block_size
        if len(tokens) < nblocks * bs:
            raise ValueError(
                f"payload carries {nblocks} block(s) but only "
                f"{len(tokens)} token(s) key them "
                f"(block_size {bs})")
        shape = self.wire_block_shape
        got = tuple(layers[0]["k"].shape[1:])
        if len(layers) != len(self.caches) or got != shape:
            raise ValueError(
                f"payload geometry mismatch: {len(layers)} layer(s) of "
                f"blocks shaped {got}, pool has {len(self.caches)} "
                f"layer(s) shaped {shape}")
        blocks: List[int] = []
        try:
            for _ in range(nblocks):
                blocks.append(self._alloc_block(None))
        except KVBlocksExhausted:
            for b in blocks:
                self._release(b)
            raise
        idx = jnp.asarray(np.asarray(blocks, np.int32))
        payload = [{k: jnp.asarray(v) for k, v in layer.items()}
                   for layer in layers]
        if self.quantized:
            self.caches = _scatter_blocks_quantized_jit(
                self.caches, idx, payload)
        else:
            self.caches = _scatter_blocks_dequant_jit(
                self.caches, idx, payload)

        new_blocks: List[int] = []

        def take_ref(block: int) -> None:
            self._refs[block] += 1
            new_blocks.append(block)

        inserted = self.trie.insert(
            list(int(t) for t in tokens)[:nblocks * bs], blocks, take_ref)
        if origin == "peer":
            self._peer_blocks.update(new_blocks)
        # Drop our allocation refs: blocks the trie took stay cached at
        # ref 1 (the trie's); blocks it already had under the same
        # token path return to the free list (first writer won).
        for b in blocks:
            self._release(b)
        return inserted

    # ------------------------------------------------------- accounting
    def clear_prefix_cache(self) -> int:
        """Drop every cached block (knob flips / tests). -> count."""
        return self.trie.clear(self._release)

    def leak_check(self) -> None:
        """Assert the ref-count books balance: every non-free block is
        explained by slot bindings + trie nodes, and freeing everything
        would empty the pool. Chaos tests call this after drain.

        Quantized pools additionally assert the scale buffers kept
        their block-indexed shape: a block and its scale row share one
        index into the same pytree, which is what makes "COW carries
        scales" and "eviction frees scales" true by construction — a
        shape drift here would mean some path rebuilt the caches tree
        without them."""
        if self.quantized:
            for li, layer in enumerate(self.caches):
                for kv in ("k", "v"):
                    if jnp.dtype(layer[kv].dtype) != jnp.int8:
                        raise AssertionError(
                            f"layer {li} {kv} pool dtype drifted to "
                            f"{layer[kv].dtype} (expected int8)")
                    sc = layer.get(f"{kv}_scale")
                    want = (self.num_blocks, self.wire_block_shape[0])
                    if sc is None or tuple(sc.shape) != want:
                        raise AssertionError(
                            f"layer {li} {kv}_scale buffer missing or "
                            f"mis-shaped: "
                            f"{None if sc is None else sc.shape} "
                            f"(expected {list(want)})")
        # Host-tier column of the oracle: entry count within budget,
        # byte books balanced, every entry shaped like this pool's
        # blocks and keyed by a whole number of full blocks. A drift
        # here means a demote/promote path moved payloads without
        # moving the accounting — the host-side twin of a ref leak.
        if self.host_blocks or self._host_tier:
            if len(self._host_tier) > self.host_blocks:
                raise AssertionError(
                    f"host tier holds {len(self._host_tier)} entries, "
                    f"budget {self.host_blocks} — the LRU cap leaked")
            nbytes = sum(self._entry_bytes(e)
                         for e in self._host_tier.values())
            if nbytes != self._host_bytes:
                raise AssertionError(
                    f"host tier byte books off: {self._host_bytes} "
                    f"recorded, {nbytes} resident")
            shape = self.wire_block_shape
            for key, entry in self._host_tier.items():
                if len(key) % self.block_size or \
                        len(key) // self.block_size == 0:
                    raise AssertionError(
                        f"host tier key length {len(key)} is not a "
                        f"whole number of blocks (bs {self.block_size})")
                if (len(entry) != len(self.caches)
                        or tuple(entry[0]["k"].shape) != (1,) + shape):
                    raise AssertionError(
                        f"host tier entry geometry drifted: "
                        f"{len(entry)} layer(s) shaped "
                        f"{tuple(entry[0]['k'].shape)}, pool has "
                        f"{len(self.caches)} layer(s) of [1, "
                        f"{', '.join(str(s) for s in shape)}] blocks")
        expect = np.zeros((self.num_blocks,), np.int64)
        for slot in range(self.capacity):
            if slot in self._free_slots:
                continue
            for i in range(int(self._bound[slot])):
                expect[self.tables_host[slot, i]] += 1
        for b in self.trie.blocks:
            expect[b] += 1
        expect[0] = 0
        self._books.check(expect, "KV block")
        # The window and state groups: a taken slot holds exactly its
        # ring and its state entry, a free slot's rows are scratch, and
        # nothing else references an entry of either.
        for books, table, what in (
                (self._ring_books, self.window_tables_host,
                 "window ring block"),
                (self._state_books, self.state_tables_host, "state entry")):
            held = np.zeros((books.num_blocks,), np.int64)
            for slot in range(self.capacity):
                row = table[slot]
                if slot in self._free_slots:
                    if row.any():
                        raise AssertionError(
                            f"free slot {slot} still names {what}s "
                            f"{row.tolist()}")
                elif row.size and not row.all():
                    raise AssertionError(
                        f"slot {slot} has unbound {what}s: {row.tolist()}")
                np.add.at(held, row, 1)
            held[0] = 0
            books.check(held, what)
        # Fleet peer tags (PR 17) may only name blocks somebody still
        # holds: a tag on a freed block would mis-count an unrelated
        # future binding as a peer hit.
        untagged = [b for b in self._peer_blocks if self._refs[b] <= 0]
        if untagged:
            raise AssertionError(
                f"peer tier tags leaked past release: blocks "
                f"{sorted(untagged)} are tagged but free")
        if self.mirror is not None:
            # Draft-pool extension of the oracle: the mirror's slot
            # free-list must agree with ours slot for slot (lifecycle
            # lockstep), and its own block books must balance too.
            if sorted(self.mirror._free_slots) != sorted(self._free_slots):
                raise AssertionError(
                    f"draft pool slot drift: mirror free "
                    f"{sorted(self.mirror._free_slots)} != "
                    f"{sorted(self._free_slots)}")
            self.mirror.leak_check()
