"""Block-paged KV cache with a free-list allocator (the vLLM/Ragged-Paged-
Attention memory model, re-grown for this stack; PAPERS.md).

The dense decode path (`GPTForCausalLM.init_caches`) allocates a
``[B, S_max, H*D]`` ring per request — O(S_max) HBM per request no matter
how short the request actually is.  `BlockKVCache` instead pools K/V in
fixed-size physical blocks

    k_blocks[l], v_blocks[l] : [num_blocks, block_size, H*D]   per layer

and gives each sequence a *block table* (list of physical block ids), so
a request holds exactly ``ceil(len / block_size)`` blocks and frees them
the moment it finishes.  The device arrays are plain jax buffers owned by
this object; the engine's jitted step takes them donated and returns the
updated pool.  Heads stay flattened in the last axis, the shape the
ragged kernel DMAs a block in: on a TPU an array is tiled over its last
two axes, so a pool kept as ``[.., H, D]`` is relaid whole
(``[.., bs, H*D]`` is another tiling of the same bytes) around every
kernel call.  Readers that need heads split ``H*D -> (H, D)`` on the
rows they gathered, never on the pool.

Allocator design (host-side, O(1) per op):

- **free list** — LIFO stack of physical ids; `Block` objects carry a
  refcount.
- **copy-on-fork** — `fork(parent, child)` shares every parent block by
  bumping refcounts (shared-prompt serving: N continuations of one prompt
  pay its KV once).  The first append into a SHARED last block triggers
  copy-on-write: a fresh block is allocated and the shared content copied
  device-side (`_copy_block`).
- **preemption by eviction** — `swap_out(seq)` snapshots the sequence's
  block contents to host numpy and frees the blocks; `swap_in(seq)`
  restores them bit-exactly into freshly allocated blocks.  Bit-exact
  restore is what makes "preempted requests resume with identical
  output" a guarantee instead of a tolerance (a recompute-from-prompt
  resume would re-run prefill over a different chunk length and shift
  last-ulp floats).

Every transition asserts the refcount/free-list invariants — the
allocator can never hand out a block that is still referenced
(tests/test_serving.py fuzzes this).

**Automatic prefix caching** (ISSUE 15 — the engine opts in via
``EngineConfig(enable_prefix_caching=True)``; with nobody registering,
nothing below changes behaviour):

- **prefix index** — a hash-keyed map over FULL blocks.  Keys are
  *chained* content digests (`prefix_block_keys`): block j's key hashes
  (key_{j-1}, tokens_of_block_j), so one key identifies an entire
  block-aligned token prefix — the radix-trie-equivalent over block
  hashes.  sha1 digests, not python ``hash()``: a collision would adopt
  WRONG KV silently, and int-tuple hashes are also what PYTHONHASHSEED
  reseeding taught PR 2 to distrust.
- **adoption** — `match_prefix` walks the chain to the longest indexed
  prefix; `adopt_prefix` builds a new sequence's table from those
  physical blocks by refcount bump — N requests sharing a system prompt
  pay its prefill ONCE.  Only FULL blocks are ever indexed/adopted (a
  full block is never written again while referenced, so sharing needs
  no CoW), and adoption is capped below the full prompt by the caller
  (the last prompt token must be recomputed for its logits).
- **LRU parking** — a block whose refcount drops to 0 while indexed is
  PARKED on an LRU instead of the free list: its content stays adoptable
  and it is reclaimed LAST (`_take` drains the free list first, then
  evicts the least-recently-used parked block, dropping its index
  entry).  Parked blocks count as allocatable capacity
  (`num_free_blocks`) but NOT as free for the utilization gauges
  (`blocks_in_use` includes them — they hold live, reusable bytes).
- observability: `serving/prefix_hits` / `prefix_hit_tokens` /
  `prefix_evictions` counters (monitor-gated no-ops when PTPU_MONITOR
  is off) plus the plain-int twins on the instance.  The memory
  microscope (ISSUE 20) adds a per-pool lifecycle ledger
  (``self.acct``, `monitor.memory.KVAccounting`): every transition —
  alloc/free/fork/cow/park/adopt/evict/swap_out/swap_in — counts under
  ``serving/kv_blocks{event}``, parked blocks carry their park
  timestamp (the residency-age forensics), and every capacity view
  (`num_free_blocks` / `num_parked_blocks` / `blocks_in_use` /
  `utilization`) derives from the ONE `counts()` source so the
  utilization gauge and the admission budget can never drift apart.

**Speculative-decode rollback** (`truncate_to`): the verify step
reserves blocks for up to k draft positions; rejected drafts roll the
table back by releasing the surplus blocks — slots inside kept blocks
that held rejected K/V are re-written by later real tokens before any
mask lets a query read them.

**Window groups** (``window=W``, ISSUE 28): the pool of a model's
sliding-window layers.  A key further than W behind the newest query is
never read again, so before every step the table gives back each block
that lies wholly behind ``length + 1 - W`` (`grow_to`): a sequence holds
at most ``ceil(W / block_size) + 1`` blocks however long it grows.  The
table keeps every LOGICAL index - a released entry holds ``num_blocks``,
the id that points nowhere (slots made from it are dropped, gathers of it
are masked by the window) - so position p still lives at
``table[p // block_size]``.  A whole-prompt prefill longer than the
window is given blocks for its tail only (`allocate(tail_only=True)`,
`tail_start`).  Swap-out saves the live blocks and their logical indices.
Copy-on-fork, prefix caching, speculative roll-back and int8 are not
carried over a window group and raise.

`CacheGroups` puts the caches of a model's layer groups (one id space,
pool shape and table each) behind the allocator calls the scheduler
makes: each call reaches every group or none.

**State groups** (`StateCache`, ISSUE 32): the memory of layers that keep
no K/V, a tensor of fixed size a sequence whatever its length (a gated
short convolution's last two inputs).  A pool ``[slots + 1, *shape]`` a
layer, a SLOT a sequence instead of blocks a token, behind the same
allocator calls: `allocate` takes one slot and zeroes it, `grow_to` and
`truncate_to` cost nothing, `swap_out` / `swap_in` and `fork` copy it
bit-exactly.  The last slot belongs to nobody: padding rows of a
fixed-shape program read and write it.

**Latent groups** (``value_in_key=True``, ISSUE 34): the pool of a model's
latent-attention layers.  A token's row is its latent, the key every
query head scores against and - its leading lanes - the value every head
sums, so a layer keeps ONE pool (``k_blocks[l]``; ``v_blocks`` is None and
`pool_names` is ``("k_blocks",)``): ``[num_blocks, block_size, lanes]``
with `lanes` the row padded to whole lane tiles
(`ops.paged_attention.latent_pool_lanes`: 320 -> 384).  Every allocator
path is the full group's - blocks a token, copy-on-fork, swap-out and
swap-in bit for bit (a snapshot holds ``"k"`` alone) - because each works
over `pool_names`; int8 and a window are not carried and raise.

**Quantized mode** (``kv_quant="int8"``, the `paddle_tpu.lowbit` KV
wing): pools store int8 codes plus per-block-per-head float32 scales
(``k_scales[l], v_scales[l] : [num_blocks, num_heads]``, value =
code·scale).  A block costs ``block_size·H·D + 4·H`` bytes instead of
``block_size·H·D·itemsize`` — ~¼ of fp32, ~½ of bf16 — so the same pool
byte budget holds ~2–4× the blocks (`block_bytes` does the accounting;
the engine sizes the default pool by BYTES, not block count).  Scales
ride every block operation: copied on CoW, saved/restored through
swap_out/swap_in (bit-stable in the quantized domain), and zeroed when a
block is reallocated (`_reset_scales`).
"""
from __future__ import annotations

import functools
import hashlib
import struct
import time
from collections import OrderedDict

import numpy as np
import jax
import jax.numpy as jnp

from .. import monitor
from ..monitor import memory as mmemory

__all__ = ["BlockKVCache", "BlockAllocatorError", "CacheGroups",
           "StateCache", "prefix_block_keys"]


# a pool's key in a `swap_out` snapshot
_SAVED_AS = {"k_blocks": "k", "v_blocks": "v", "k_scales": "ks",
             "v_scales": "vs"}


class BlockAllocatorError(RuntimeError):
    pass


def prefix_block_keys(token_ids, block_size) -> list:
    """Chained content keys for every FULL block of `token_ids`.

    key_j = sha1(key_{j-1} || tokens[j*bs:(j+1)*bs]) — equal keys imply
    equal block-aligned token prefixes, so a single dict lookup per block
    walks the radix-trie-equivalent.  Deterministic across processes
    (PYTHONHASHSEED-free) and collision-safe in practice (adopting on a
    collision would serve another prompt's KV)."""
    bs = int(block_size)
    keys = []
    prev = b""
    for j in range(len(token_ids) // bs):
        block = token_ids[j * bs:(j + 1) * bs]
        prev = hashlib.sha1(
            prev + struct.pack(f"<{bs}q", *[int(t) for t in block])
        ).digest()
        keys.append(prev)
    return keys


class _Block:
    __slots__ = ("idx", "ref")

    def __init__(self, idx):
        self.idx = idx
        self.ref = 0


class BlockKVCache:
    """Per-layer K/V pools ``[num_blocks, block_size, num_heads *
    head_dim]`` (int8 codes plus ``[num_blocks, num_heads]`` float32
    scales under ``kv_quant="int8"``) and the host-side allocator over
    their blocks.  One pool shape, whatever reads it."""

    def __init__(self, num_layers, num_blocks, block_size, num_heads,
                 head_dim, dtype=jnp.float32, kv_quant=None, window=None,
                 name="full", value_in_key=False):
        if kv_quant not in (None, "int8"):
            raise ValueError(
                f'kv_quant must be None or "int8", got {kv_quant!r}')
        if window is not None and kv_quant:
            raise ValueError('kv_quant="int8" is not carried over a '
                             "window group")
        if value_in_key and (kv_quant or window is not None):
            raise ValueError("a latent group is kept at full precision "
                             "and without a window")
        # a latent group (ISSUE 34): a token's row is its key and, in its
        # leading lanes, its value, so a layer keeps ONE pool (`k_blocks`)
        self.value_in_key = bool(value_in_key)
        self.window = None if window is None else int(window)
        self.name = name
        self.released = 0      # blocks given back from behind the window
        self.num_layers = int(num_layers)
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.dtype = dtype
        self.kv_quant = kv_quant
        shape = (self.num_blocks, self.block_size,
                 self.num_heads * self.head_dim)
        pool_dtype = jnp.int8 if kv_quant else dtype
        self.k_blocks = [jnp.zeros(shape, pool_dtype)
                         for _ in range(num_layers)]
        self.v_blocks = None if value_in_key else [
            jnp.zeros(shape, pool_dtype) for _ in range(num_layers)]
        if kv_quant:
            # per-block-per-head abs-max scales: value = code * scale
            sshape = (self.num_blocks, self.num_heads)
            self.k_scales = [jnp.zeros(sshape, jnp.float32)
                             for _ in range(num_layers)]
            self.v_scales = [jnp.zeros(sshape, jnp.float32)
                             for _ in range(num_layers)]
        else:
            self.k_scales = self.v_scales = None
        self._blocks = [_Block(i) for i in range(self.num_blocks)]
        self._free = list(range(self.num_blocks - 1, -1, -1))  # LIFO
        self._tables: dict = {}        # seq_id -> [physical ids]
        self._lengths: dict = {}       # seq_id -> token count covered
        self.peak_blocks_in_use = 0
        # ISSUE 20 memory microscope: per-pool lifecycle ledger
        # (serving/kv_blocks{event} + parked-residency histogram) — one
        # module-global check per hook when PTPU_MEMOBS is off
        self.acct = mmemory.KVAccounting()
        # -- prefix cache (ISSUE 15; inert until register_prefix) ----------
        self._prefix_index: dict = {}  # chain key (bytes) -> physical id
        self._block_key: dict = {}     # physical id -> chain key
        self._chain_of: dict = {}      # physical id -> chain id (the
        #                                register_prefix registration it
        #                                was indexed under — groups the
        #                                /kv "parked chains" view)
        self._lru: "OrderedDict" = OrderedDict()   # parked id ->
        #                                monotonic park timestamp, LRU
        #                                first (the timestamp feeds the
        #                                residency-age forensics)
        self.prefix_hits = 0
        self.prefix_hit_tokens = 0
        self.prefix_evictions = 0
        self._m_hits = monitor.counter(
            "serving/prefix_hits", "requests that adopted cached prefix "
            "blocks at admission")
        self._m_hit_toks = monitor.counter(
            "serving/prefix_hit_tokens",
            "prompt tokens whose prefill was paid by a cached prefix")
        self._m_evict = monitor.counter(
            "serving/prefix_evictions",
            "parked prefix blocks reclaimed for fresh allocations")

    # -- introspection ------------------------------------------------------

    @property
    def pool_names(self) -> tuple:
        """The attributes that hold a layer's device arrays, in the order
        the engine's programs take and return them."""
        if self.value_in_key:
            return ("k_blocks",)
        return ("k_blocks", "v_blocks") + (
            ("k_scales", "v_scales") if self.kv_quant else ())

    @staticmethod
    def block_bytes(block_size, num_heads, head_dim, dtype=jnp.float32,
                    kv_quant=None, pools=2) -> int:
        """Bytes ONE physical block costs per layer (K + V pools, plus the
        per-block-per-head f32 scales when quantized; `pools=1`: a latent
        group's one pool)."""
        per_tok = int(num_heads) * int(head_dim)
        if kv_quant == "int8":
            return 2 * (int(block_size) * per_tok + 4 * int(num_heads))
        return pools * int(block_size) * per_tok * np.dtype(dtype).itemsize

    @property
    def bytes_per_block(self) -> int:
        """Bytes one block costs across all layers."""
        return self.num_layers * self.block_bytes(
            self.block_size, self.num_heads, self.head_dim, self.dtype,
            self.kv_quant, pools=1 if self.value_in_key else 2)

    @property
    def pool_bytes(self) -> int:
        return self.num_blocks * self.bytes_per_block

    @property
    def num_slots(self) -> int:
        """Total physical token slots — also the ragged kernel's
        "dropped write" sentinel: a slot id >= num_slots marks a padding
        / evicted row whose write must be discarded, never clamped.
        (The per-row true lengths the kernel bounds its block stream by
        come from the engine's Request state — `req.total_len` is the
        authoritative value at decode time.)"""
        return self.num_blocks * self.block_size

    def counts(self) -> dict:
        """The ONE accounting source every capacity view derives from
        (ISSUE 20 satellite: the utilization gauge and the admission-
        capacity view were computed in two places and could drift).
        Invariants: ``free + in_use == total`` and
        ``allocatable == free + parked`` — parked prefix blocks are
        allocatable (reclaimed last by `_take`) but IN-USE for the
        utilization view (they hold live, reusable bytes)."""
        free = len(self._free)
        parked = len(self._lru)
        return {
            "total": self.num_blocks,
            "free": free,
            "parked": parked,
            "allocatable": free + parked,
            "in_use": self.num_blocks - free,
            "referenced": self.num_blocks - free - parked,
            "peak_in_use": self.peak_blocks_in_use,
        }

    @property
    def num_free_blocks(self) -> int:
        """ALLOCATABLE blocks: truly free plus LRU-parked prefix blocks
        (parked blocks are reclaimed — last — by `_take`), the number
        admission decisions budget against."""
        return self.counts()["allocatable"]

    @property
    def num_parked_blocks(self) -> int:
        """Unreferenced blocks held by the prefix index (adoptable AND
        reclaimable)."""
        return self.counts()["parked"]

    @property
    def blocks_in_use(self) -> int:
        """Blocks holding live bytes — referenced OR parked.  Parked
        prefix blocks are deliberately counted in-use: the utilization
        gauges must not report reusable-cache bytes as free capacity."""
        return self.counts()["in_use"]

    @property
    def utilization(self) -> float:
        """`serving/block_utilization`'s value, derived from the same
        `counts()` source as every other capacity view."""
        c = self.counts()
        return c["in_use"] / max(c["total"], 1)

    def block_table(self, seq_id):
        return list(self._tables[seq_id])

    def slot(self, seq_id, position) -> int:
        """Physical slot of an (allocated) token position."""
        t = self._tables[seq_id]
        return t[position // self.block_size] * self.block_size \
            + position % self.block_size

    def blocks_needed(self, num_tokens) -> int:
        return -(-int(num_tokens) // self.block_size)

    # -- window groups ------------------------------------------------------

    def _first_live(self, length) -> int:
        """First logical block a step still reads when the sequence holds
        `length` tokens before it: the step's first query, at position
        `length`, sees keys from `length + 1 - window` on."""
        if self.window is None:
            return 0
        return max(0, int(length) + 1 - self.window) // self.block_size

    def tail_start(self, num_tokens) -> int:
        """First position a whole-prompt prefill of `num_tokens` writes
        into this group: 0, or the start of the first block the decode
        step after it still reads."""
        return self._first_live(num_tokens) * self.block_size

    def _live(self, table) -> list:
        return [i for i in table if i < self.num_blocks]

    def _new_blocks(self, seq_id, num_tokens, tail_only=False) -> int:
        """Blocks `allocate` / `grow_to` would take, less those `grow_to`
        gives back first."""
        t = self._tables.get(seq_id)
        if t is None:
            first = self._first_live(num_tokens) if tail_only else 0
            return self.blocks_needed(num_tokens) - first
        need = self.blocks_needed(num_tokens) - len(t)
        if self.window is not None:
            need -= len(self._live(
                t[:self._first_live(self._lengths[seq_id])]))
        return need

    def can_allocate(self, num_tokens, tail_only=False) -> bool:
        """Room for a fresh sequence of `num_tokens`?  `tail_only`: the
        step is a whole-prompt prefill, which reads its keys from the
        chunk, so a window group holds the tail alone."""
        return self._new_blocks(None, num_tokens, tail_only) \
            <= self.num_free_blocks

    def fits_empty(self, seq_id, num_tokens) -> bool:
        """Could the EMPTY pool cover `num_tokens` for this sequence?"""
        need = self.blocks_needed(num_tokens)
        if self.window is not None:
            need = min(need, self.blocks_needed(self.window) + 1)
        if self._needs_cow(seq_id, num_tokens):
            need += 1
        return need <= self.num_blocks

    def live_tokens(self, length) -> int:
        """Tokens a decode step over `length` keys reads from this
        group."""
        return int(length) if self.window is None \
            else min(int(length), self.window)

    # -- allocate / grow / free --------------------------------------------

    def _take(self) -> int:
        if self._free:
            i = self._free.pop()
        elif self._lru:
            # reclaimed LAST, least-recently-used first: the parked block
            # stops being adoptable the moment its bytes are handed out
            i, parked_ts = self._lru.popitem(last=False)
            self._drop_index(i)
            self.prefix_evictions += 1
            self._m_evict.inc()
            self.acct.on("evict")
            if parked_ts is not None:
                self.acct.observe_residency(
                    max(0.0, time.monotonic() - parked_ts))
        else:
            raise BlockAllocatorError("out of KV blocks")
        blk = self._blocks[i]
        assert blk.ref == 0, f"free list handed out a referenced block {i}"
        blk.ref = 1
        self.acct.on("alloc")
        self.peak_blocks_in_use = max(self.peak_blocks_in_use,
                                      self.blocks_in_use)
        return i

    def _release(self, idx):
        blk = self._blocks[idx]
        assert blk.ref > 0, f"double free of block {idx}"
        blk.ref -= 1
        if blk.ref == 0:
            if idx in self._block_key:
                # indexed prefix block: park (content stays adoptable)
                self._lru[idx] = time.monotonic()
                self._lru.move_to_end(idx)
                self.acct.on("park")
            else:
                self._free.append(idx)
                self.acct.on("free")

    def _drop_index(self, idx) -> None:
        key = self._block_key.pop(idx, None)
        if key is not None:
            self._prefix_index.pop(key, None)
        self._chain_of.pop(idx, None)

    def _needs_cow(self, seq_id, num_tokens) -> bool:
        """Will growing to `num_tokens` write into a SHARED partially-
        filled last block?  (A full shared block is never written again —
        new tokens land in fresh blocks — so it can stay shared.)"""
        if self.window is not None:
            return False           # never forked, so never shared
        t = self._tables.get(seq_id)
        old = self._lengths.get(seq_id, 0)
        return bool(t) and num_tokens > old \
            and old % self.block_size != 0 \
            and self._blocks[t[-1]].ref > 1

    def can_grow_to(self, seq_id, num_tokens) -> bool:
        """Enough free blocks (plus a possible copy-on-write block) to
        cover `num_tokens` for this sequence?"""
        need = self._new_blocks(seq_id, num_tokens)
        if self._needs_cow(seq_id, num_tokens):
            need += 1              # CoW of the shared last block
        return need <= self.num_free_blocks

    def allocate(self, seq_id, num_tokens, tail_only=False):
        """Register `seq_id` and give it blocks covering `num_tokens`
        (`tail_only`: see `can_allocate`)."""
        if seq_id in self._tables:
            raise BlockAllocatorError(f"sequence {seq_id} already allocated")
        need = self._new_blocks(None, num_tokens, tail_only)
        if need > self.num_free_blocks:
            raise BlockAllocatorError("out of KV blocks")
        ids = [self._take() for _ in range(need)]
        nowhere = self.blocks_needed(num_tokens) - need
        self._tables[seq_id] = [self.num_blocks] * nowhere + ids
        self._lengths[seq_id] = int(num_tokens)
        self._reset_scales(ids)

    def grow_to(self, seq_id, num_tokens):
        """Extend a sequence's table to cover `num_tokens` tokens,
        copy-on-writing a shared partially-filled last block first (the
        append target must be privately owned — forked siblings keep
        reading the original)."""
        t = self._tables[seq_id]
        if self._needs_cow(seq_id, num_tokens):
            self._cow_last_block(seq_id)
        if self.window is not None:
            # give back what lies wholly behind the window of this step's
            # first query; the entry keeps its place and points nowhere
            for j in range(min(self._first_live(self._lengths[seq_id]),
                               len(t))):
                if t[j] < self.num_blocks:
                    self._release(t[j])
                    t[j] = self.num_blocks
                    self.released += 1
        new_ids = []
        while len(t) < self.blocks_needed(num_tokens):
            new_ids.append(self._take())
            t.append(new_ids[-1])
        self._lengths[seq_id] = max(self._lengths[seq_id], int(num_tokens))
        self._reset_scales(new_ids)

    def free(self, seq_id):
        for idx in self._live(self._tables.pop(seq_id)):
            self._release(idx)
        self._lengths.pop(seq_id, None)

    def truncate_to(self, seq_id, num_tokens):
        """Shrink a sequence's table to cover exactly `num_tokens` tokens
        — the speculative-decode rollback: blocks reserved for rejected
        draft positions are released (decref — a shared block survives
        for its other holders).  Slots inside KEPT blocks that held
        rejected K/V are overwritten by later real tokens before any
        causal mask lets a query read them."""
        self._no_window("truncate_to (speculative roll-back)")
        t = self._tables[seq_id]
        keep = self.blocks_needed(num_tokens)
        while len(t) > keep:
            self._release(t.pop())
        self._lengths[seq_id] = min(self._lengths[seq_id],
                                    int(num_tokens))

    # -- copy-on-fork -------------------------------------------------------

    def fork(self, parent_id, child_id):
        """Share the parent's blocks with a new sequence (refcount bump —
        no copy until one of them appends into the shared last block)."""
        self._no_window("fork")
        if child_id in self._tables:
            raise BlockAllocatorError(f"sequence {child_id} already exists")
        t = self._tables[parent_id]
        for idx in t:
            self._blocks[idx].ref += 1
        self._tables[child_id] = list(t)
        self._lengths[child_id] = self._lengths[parent_id]
        self.acct.on("fork", len(t))

    def _no_window(self, what):
        if self.window is not None:
            raise BlockAllocatorError(
                f"{what} is not carried over a window group "
                f"(group {self.name!r}, window {self.window})")

    def _reset_scales(self, ids):
        """Zero the quant scales of freshly (re)allocated blocks — a
        block's scale only grows while it is owned, so a reallocated
        block must not inherit the previous owner's dynamic range."""
        if not self.kv_quant or not ids:
            return
        idx = jnp.asarray(ids, jnp.int32)
        for l in range(self.num_layers):
            self.k_scales[l] = self.k_scales[l].at[idx].set(0.0)
            self.v_scales[l] = self.v_scales[l].at[idx].set(0.0)

    def _copy_block(self, src, dst):
        for name in self.pool_names:
            pools = getattr(self, name)
            for l in range(self.num_layers):
                pools[l] = pools[l].at[dst].set(pools[l][src])

    def _cow_last_block(self, seq_id):
        t = self._tables[seq_id]
        src = t[-1]
        dst = self._take()
        self._copy_block(src, dst)
        t[-1] = dst
        self._release(src)
        self.acct.on("cow")

    # -- automatic prefix caching (ISSUE 15) --------------------------------

    def register_prefix(self, seq_id, keys, num_tokens) -> None:
        """Index `seq_id`'s fully-written leading blocks under their
        chain keys (`prefix_block_keys` of the prompt).  Only blocks
        wholly inside the first `num_tokens` computed tokens are indexed
        — a full block is never written again while referenced, so its
        content is final.  First writer wins: an existing key keeps
        pointing at the original block (dedup, not re-pointing)."""
        self._no_window("prefix caching")
        t = self._tables[seq_id]
        full = min(len(keys), int(num_tokens) // self.block_size, len(t))
        # chain id: the chain's FIRST key names the whole registration
        # (stable across re-registrations — first writer wins below), so
        # the /kv pool map can group parked blocks back into the prompt
        # chain they came from (ISSUE 20)
        chain = keys[0].hex()[:12] if full else None
        for j in range(full):
            key = keys[j]
            if key in self._prefix_index:
                continue
            idx = t[j]
            if idx in self._block_key:
                continue   # already indexed under another chain
            self._prefix_index[key] = idx
            self._block_key[idx] = key
            self._chain_of[idx] = chain

    def match_prefix(self, keys, max_blocks=None) -> int:
        """Longest indexed prefix of `keys`, in blocks.  Walks the chain
        in order and stops at the first miss; refreshes the recency of
        every parked block it matches."""
        limit = len(keys) if max_blocks is None else min(len(keys),
                                                        int(max_blocks))
        n = 0
        for j in range(limit):
            idx = self._prefix_index.get(keys[j])
            if idx is None:
                break
            if idx in self._lru:
                self._lru.move_to_end(idx)
            n += 1
        return n

    def adoptable_free_blocks(self, keys, n_blocks) -> int:
        """`num_free_blocks` minus the first `n_blocks` matched blocks
        that are currently PARKED — adopting those revives them, so an
        admission check must not count them as reclaimable capacity
        too (the double-count would admit a request that cannot fit)."""
        parked = sum(1 for key in keys[:n_blocks]
                     if self._prefix_index.get(key) in self._lru)
        return self.num_free_blocks - parked

    def adopt_prefix(self, seq_id, keys, n_blocks) -> int:
        """Start `seq_id` from the cached chain: its table begins with
        the `n_blocks` indexed physical blocks (refcount bump — parked
        blocks are revived off the LRU; no bytes move).  Returns the
        adopted token count, which the caller records as the sequence's
        already-computed prefix."""
        if seq_id in self._tables:
            raise BlockAllocatorError(f"sequence {seq_id} already exists")
        ids = []
        for key in keys[:n_blocks]:
            idx = self._prefix_index[key]
            blk = self._blocks[idx]
            if blk.ref == 0:
                self._lru.pop(idx, None)
            blk.ref += 1
            ids.append(idx)
        self._tables[seq_id] = ids
        hit_tokens = len(ids) * self.block_size
        self._lengths[seq_id] = hit_tokens
        self.acct.on("adopt", len(ids))
        self.peak_blocks_in_use = max(self.peak_blocks_in_use,
                                      self.blocks_in_use)
        if ids:
            self.prefix_hits += 1
            self.prefix_hit_tokens += hit_tokens
            self._m_hits.inc()
            self._m_hit_toks.inc(hit_tokens)
        return hit_tokens

    def privatize_last_block(self, seq_id):
        """Copy the sequence's last block now if it is shared.  A forked
        child RE-WRITES its final inherited position (it re-feeds the
        parent's last sampled token through its own prefill), and that
        slot must never land in a block the parent still reads — two
        jitted programs recomputing the same K/V may differ in the last
        ulp."""
        t = self._tables[seq_id]
        if t and self._blocks[t[-1]].ref > 1:
            self._cow_last_block(seq_id)

    # -- preemption swap ----------------------------------------------------

    def swap_out(self, seq_id):
        """Evict: host-snapshot the sequence's block contents and free its
        blocks.  Returns the opaque saved state for `swap_in`."""
        t = self._tables[seq_id]
        live = self._live(t)
        idx = np.asarray(live, np.int32)
        # by pool, under the snapshot's keys: "k", "v" (a latent group
        # has no second pool), and under int8 the scales "ks", "vs" -
        # codes alone are meaningless, the scales ARE the values'
        # exponents; saving both is what keeps the quantized domain
        # bit-stable across evict/restore
        saved = {"len": self._lengths[seq_id]}
        for name in self.pool_names:
            saved[_SAVED_AS[name]] = [np.asarray(p[idx])
                                      for p in getattr(self, name)]
        if self.window is not None:
            # where each saved block sits in the table, and its width
            saved["logical"] = [j for j, i in enumerate(t)
                                if i < self.num_blocks]
            saved["width"] = len(t)
        self.acct.on("swap_out", len(live))
        self.free(seq_id)
        return saved

    @staticmethod
    def swap_blocks(saved) -> int:
        """Blocks a snapshot needs to come back."""
        return len(saved["k"][0])

    def can_swap_in(self, saved) -> bool:
        return self.swap_blocks(saved) <= self.num_free_blocks

    def swap_in(self, seq_id, saved):
        """Restore an evicted sequence bit-exactly into fresh blocks."""
        n = len(saved["k"][0])
        if n > self.num_free_blocks:
            raise BlockAllocatorError("out of KV blocks")
        self.acct.on("swap_in", n)
        ids = [self._take() for _ in range(n)]
        if "logical" in saved:
            table = [self.num_blocks] * saved["width"]
            for j, i in zip(saved["logical"], ids):
                table[j] = i
        else:
            table = ids
        self._tables[seq_id] = table
        self._lengths[seq_id] = saved["len"]
        idx = jnp.asarray(ids, jnp.int32)
        for name in self.pool_names:
            pools = getattr(self, name)
            for l in range(self.num_layers):
                pools[l] = pools[l].at[idx].set(
                    jnp.asarray(saved[_SAVED_AS[name]][l]))



@functools.partial(jax.jit, donate_argnums=(0,))
def _write_slot(pools, slot, rows):
    """Row `slot` of every layer's pool := that layer's `rows` entry."""
    return [p.at[slot].set(r) for p, r in zip(pools, rows)]


class StateCache:
    """A state group: per layer a pool ``[num_slots + 1, *shape]`` and a
    host-side free list over its slots, behind the allocator calls of
    `BlockKVCache` that `CacheGroups` fans out.  A sequence holds ONE slot
    whatever its length; slot `num_slots` is the dropped slot of padding
    rows and is never handed out."""

    pool_names = ("state",)
    block_size = None              # a slot is not made of blocks

    def __init__(self, num_layers, num_slots, shape, dtype, name="state"):
        self.name = name
        self.num_layers = int(num_layers)
        self.num_slots = int(num_slots)
        self.shape = tuple(int(n) for n in shape)
        self.dtype = dtype
        self.state = [jnp.zeros((self.num_slots + 1,) + self.shape, dtype)
                      for _ in range(self.num_layers)]
        self._zeros = [jnp.zeros(self.shape, dtype)] * self.num_layers
        self._free = list(range(self.num_slots - 1, -1, -1))   # LIFO
        self._tables: dict = {}        # seq_id -> slot
        swaps = monitor.counter(
            "serving/state_swaps",
            "sequences whose state slot was saved to / restored from the "
            "host")
        self._m_swap = {d: swaps.labels(dir=d) for d in ("out", "in")}

    # -- introspection ------------------------------------------------------

    @property
    def pool_bytes(self) -> int:
        return sum(s.size * s.dtype.itemsize for s in self.state)

    @property
    def slots_in_use(self) -> int:
        return self.num_slots - len(self._free)

    @property
    def num_free_blocks(self) -> int:
        """Free SLOTS: what this group can still admit."""
        return len(self._free)

    # what the scheduler's messages read of a model that has state groups
    # alone: its unit of memory is a slot
    @property
    def num_blocks(self) -> int:
        return self.num_slots

    def blocks_needed(self, num_tokens) -> int:
        return 1

    def slot_of(self, seq_id) -> int:
        return self._tables[seq_id]

    # -- the allocator calls (token counts mean nothing here) ---------------

    def can_allocate(self, num_tokens=0, tail_only=False) -> bool:
        return bool(self._free)

    def fits_empty(self, seq_id, num_tokens) -> bool:
        return self.num_slots > 0

    def can_grow_to(self, seq_id, num_tokens) -> bool:
        return seq_id in self._tables or bool(self._free)

    def _needs_cow(self, seq_id, num_tokens) -> bool:
        return False

    def _no_window(self, what):
        pass

    def _take(self, seq_id, rows) -> int:
        if seq_id in self._tables:
            raise BlockAllocatorError(f"sequence {seq_id} already allocated")
        if not self._free:
            raise BlockAllocatorError("out of state slots")
        slot = self._tables[seq_id] = self._free.pop()
        self.state = _write_slot(self.state, np.int32(slot), rows)
        return slot

    def allocate(self, seq_id, num_tokens=0, tail_only=False):
        """One slot, zeroed: a sequence starts from no history, whoever
        held the slot before."""
        self._take(seq_id, self._zeros)

    def grow_to(self, seq_id, num_tokens):
        self._tables[seq_id]           # a sequence never admitted: KeyError

    def truncate_to(self, seq_id, num_tokens):
        pass

    def privatize_last_block(self, seq_id):
        pass

    def free(self, seq_id):
        self._free.append(self._tables.pop(seq_id))

    def fork(self, parent_id, child_id):
        """The child starts from a COPY of the parent's state."""
        src = np.int32(self._tables[parent_id])
        self._take(child_id, [s[src] for s in self.state])

    # -- preemption swap ----------------------------------------------------

    def swap_out(self, seq_id):
        slot = np.int32(self._tables[seq_id])
        saved = {"state": jax.device_get([s[slot] for s in self.state])}
        self.free(seq_id)
        self._m_swap["out"].inc()
        return saved

    @staticmethod
    def swap_blocks(saved) -> int:
        return 0                       # a slot is not counted in blocks

    def can_swap_in(self, saved) -> bool:
        return bool(self._free)

    def swap_in(self, seq_id, saved):
        """Restore an evicted sequence's state bit-exactly into a slot."""
        self._take(seq_id, saved["state"])
        self._m_swap["in"].inc()


class CacheGroups:
    """The caches of a model's layer groups behind the allocator calls the
    scheduler and the engine make.  Every group has its own id space, pool
    shape and table; a sequence holds a table in each K/V group and a slot
    in each state group, and each call reaches all groups or none (the
    checks run over every group before any group is touched).  A model
    with no attention layer has state groups alone: it is admitted, kept
    and freed by slots, `block_size` is None and what counts blocks counts
    slots (`StateCache.num_blocks`)."""

    def __init__(self, groups: dict):
        self.groups = dict(groups)   # name -> BlockKVCache or StateCache
        self._all = list(self.groups.values())
        # what is counted in blocks: the K/V groups, or with none the
        # state groups, in slots
        self._kv = [c for c in self._all
                    if isinstance(c, BlockKVCache)] or self._all
        self.first = self._kv[0]
        self.block_size = self.first.block_size
        if any(c.block_size != self.block_size for c in self._kv):
            raise ValueError("cache groups share one block_size")

    # the scheduler's membership test and its messages read the first
    # group (a sequence is in every group's tables or in none)
    @property
    def _tables(self):
        return self.first._tables

    @property
    def num_blocks(self):
        return self.first.num_blocks

    @property
    def num_free_blocks(self):
        return min(c.num_free_blocks for c in self._kv)

    def blocks_needed(self, num_tokens):
        return self.first.blocks_needed(num_tokens)

    def _needs_cow(self, seq_id, num_tokens):
        return any(c._needs_cow(seq_id, num_tokens) for c in self._all)

    def can_allocate(self, num_tokens, tail_only=False):
        return all(c.can_allocate(num_tokens, tail_only) for c in self._all)

    def fits_empty(self, seq_id, num_tokens):
        return all(c.fits_empty(seq_id, num_tokens) for c in self._all)

    def can_grow_to(self, seq_id, num_tokens):
        return all(c.can_grow_to(seq_id, num_tokens) for c in self._all)

    def allocate(self, seq_id, num_tokens, tail_only=False):
        if not self.can_allocate(num_tokens, tail_only):
            raise BlockAllocatorError("out of KV blocks")
        for c in self._all:
            c.allocate(seq_id, num_tokens, tail_only)

    def grow_to(self, seq_id, num_tokens):
        if not self.can_grow_to(seq_id, num_tokens):
            raise BlockAllocatorError("out of KV blocks")
        for c in self._all:
            c.grow_to(seq_id, num_tokens)

    def free(self, seq_id):
        for c in self._all:
            c.free(seq_id)

    def swap_out(self, seq_id):
        return {"groups": {n: c.swap_out(seq_id)
                           for n, c in self.groups.items()}}

    def swap_blocks(self, saved):
        return max(c.swap_blocks(saved["groups"][n])
                   for n, c in self.groups.items())

    def can_swap_in(self, saved):
        return all(c.can_swap_in(saved["groups"][n])
                   for n, c in self.groups.items())

    def swap_in(self, seq_id, saved):
        if not self.can_swap_in(saved):
            raise BlockAllocatorError("out of KV blocks")
        for n, c in self.groups.items():
            c.swap_in(seq_id, saved["groups"][n])

    def fork(self, parent_id, child_id):
        for c in self._all:
            c._no_window("fork")
        for c in self._all:
            c.fork(parent_id, child_id)

    def privatize_last_block(self, seq_id):
        for c in self._all:
            c.privatize_last_block(seq_id)

    def truncate_to(self, seq_id, num_tokens):
        for c in self._all:
            c.truncate_to(seq_id, num_tokens)
