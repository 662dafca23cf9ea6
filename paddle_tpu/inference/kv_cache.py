"""Paged KV cache — block-pool cache for the continuous-batching server.

Reference direction: Ragged Paged Attention (arXiv:2604.15464) — the
TPU-native answer to the static-cache serving loop. Instead of one
contiguous [B, S_max] cache slab per batch (which pins every slot to the
longest possible sequence), K/V live in a pool of fixed-size blocks:

    k_blocks, v_blocks: [L, num_blocks, block_size, H * Dh]

Each sequence owns an ordered *block table* (a list of block ids); token
`t` of a sequence lives at (table[t // block_size], t % block_size), its
H heads side by side in one row of H * Dh values. That minor axis is the
pool's device layout (PR 25): a TPU pads a minor dimension of Dh = 64 to
its 128 lanes, or moves the block axis onto the lanes, and XLA then
re-laid the whole pool around every program that wrote K/V rows into it
and read [BS, H, Dh] blocks out of it. A row of H * Dh is a lane
multiple: the pool lies row-major and unpadded, the K/V scatter writes
whole rows in place and the paged kernel reads whole blocks in place
(ops/pallas/unified_attention.py).
Attention gathers keys by block table, masked by the sequence's true
length — no pad-token-value matching anywhere, so a prompt that
legitimately contains `pad_token_id` can never be corrupted.

Block 0 is a reserved *trash* block: it is never allocated, and jitted
writers route masked-out lanes (padding tail of a prefill bucket,
inactive decode slots) into it so a scatter always has a legal target.
Block tables are padded with 0 for the same reason — gathered trash
positions are masked by length before the softmax.

The pool itself is host-side bookkeeping (allocate/ensure/free on Python
ints); the device arrays are functional — jitted prefill/step functions
take them as inputs and return the updated arrays, and the cache swaps
them in via `swap_arrays`.

Prefix caching (round 9): the pool is CONTENT-ADDRESSED. A full block
holding tokens `B_i` of a sequence whose earlier blocks hash to `h_i-1`
gets the rolling prefix hash `h_i = H(h_i-1, B_i)`; an index maps hash
-> block id, and blocks carry REFCOUNTS (number of block tables
containing them). A new request whose prompt prefix matches a chain of
cached blocks is attached to them by `attach_prefix` — its block table
simply names the cached blocks (refcount bumped), so the shared prefix
is never prefilled again. The last PARTIAL block of a published prompt
is indexed too (entry carries its fill), which is what makes
conversation-continuation and identical-prompt resubmission hits
possible; writing into a shared or index-claimed region goes through
`prepare_write`, which COPIES the block first (copy-on-write) so the
cached content and every other referent stay intact. Freed blocks that
still hold indexed content are not returned to the free list — they are
parked in an LRU *retention* list and reclaimed (index entries dropped,
block freed) only when an allocation would otherwise exhaust the pool.

Quantized pool (quantized-serving round): `kv_dtype="int8"` stores the
K/V blocks as int8 codes plus a parallel per-vector scale buffer
(kv_quant.QuantizedKV, same [*, num_blocks, ...] leading layout), so
the same HBM holds ~2x the resident tokens. The block-table API is
UNCHANGED — scales ride their block index through alloc/free/CoW/
attach/retain/truncate/swap-out automatically — and the jitted
writers quantize on append while the attention kernels dequantize on
read, so a bf16 copy of the cache never exists in HBM.

Host-RAM tier (long-context serving round): attaching a
`kv_tier.HostKVTier` gives cold retained content a second life BELOW
the device pool. Pool pressure (watermark or an allocation's reclaim)
DEMOTES the LRU retained block: its index entries move to the tier's
host-side index (int8 codes+scales — bit-exact for an int8 pool,
`kv_quant` encode for a dense one) and the device slot frees. A later
`attach_prefix` / `match_prefix_len` / `export_prefix` whose chain
continues into the tier PROMOTES those entries back into device blocks
first (prefetch-on-attach: the host->device writes dispatch
asynchronously at match time, before the attach claims the chain).
Without a tier nothing changes — reclaim drops entries exactly as
before.

Invariants (fuzz-tested in tests/test_prefix_cache.py):
  * free list, retention list and the union of live block tables
    PARTITION the usable pool (block 0 in none of them);
  * `_ref[b]` equals the number of live tables containing `b`; a block
    leaves the partition's "live" class exactly when it hits zero;
  * an index entry (hash -> block, fill) only ever describes rows
    `[0, fill)` of its block, and those rows are immutable while the
    entry exists (writers CoW or drop the entry first);
  * a chain hash lives in EITHER the device index or the tier index,
    never both (promotion pops the tier entry, demotion drops the
    device entry, re-publication drops the stale tier copy).
"""
from __future__ import annotations

import functools
import hashlib
import itertools
import time
from collections import OrderedDict

import numpy as np

from ..observability import metrics as _metrics

# Pool telemetry (ISSUE 2): pushed on every alloc/grow/free, one bool
# check each while PADDLE_TPU_TELEMETRY is off. Every series carries a
# `pool` label (one per cache instance) so several live caches — the
# serving cache plus an offline generate(), say — can no longer alias
# each other's gauges.
_POOL_LABEL = ("pool",)
# Block-count gauges carry a `tier` label (long-context round):
# tier="device" is the in-pool series (the only one when no host tier
# is attached); tier="host" reports the HostKVTier — used is always 0
# there (tier content backs no live table), retained is the resident
# promotable entries, free is the remaining tier capacity.
_POOL_TIER_LABELS = ("pool", "tier")
_m_used_blocks = _metrics.gauge(
    "kv_pool_used_blocks", "allocated blocks (trash block excluded); "
    "tier='device' in-pool, tier='host' always 0",
    labelnames=_POOL_TIER_LABELS)
_m_free_blocks = _metrics.gauge(
    "kv_pool_free_blocks", "blocks available for allocation "
    "(tier='host': remaining HostKVTier entry capacity)",
    labelnames=_POOL_TIER_LABELS)
_m_retained_blocks = _metrics.gauge(
    "kv_pool_retained_blocks", "freed-but-indexed blocks parked in the "
    "prefix-cache LRU retention list (reclaimed under pool pressure); "
    "tier='host': promotable entries resident in the HostKVTier",
    labelnames=_POOL_TIER_LABELS)
_m_utilization = _metrics.gauge(
    "kv_pool_utilization", "live tokens / usable pool tokens",
    labelnames=_POOL_LABEL)
_m_block_fill = _metrics.gauge(
    "kv_pool_block_fill", "live tokens / allocated block capacity "
    "(1.0 = no internal fragmentation; can exceed 1.0 when prefix "
    "blocks are shared)", labelnames=_POOL_LABEL)
_m_sequences = _metrics.gauge(
    "kv_pool_sequences", "sequences holding blocks",
    labelnames=_POOL_LABEL)
_m_alloc_failures = _metrics.counter(
    "kv_pool_alloc_failures_total",
    "allocations refused because the pool was exhausted",
    labelnames=_POOL_LABEL)
# HBM accounting (quantized-serving round): dtype-aware, so the int8
# halving is observable per pool instead of inferred from config.
# The byte gauges carry a `shard` label (sharded-serving round):
# shard="all" is the whole-pool total; when the pool's device arrays
# are sharded over a mesh (serving_dist), per-shard series
# shard="0".."n-1" report each device's equal slice — the number that
# has to fit ONE device's HBM.
_POOL_SHARD_LABELS = ("pool", "shard")
_m_pool_bytes = _metrics.gauge(
    "kv_pool_bytes_total", "device bytes held by the K/V block pool "
    "(codes + scale buffers when kv_dtype='int8'; dtype-aware); "
    "shard='all' = pool total, shard='k' = device k's slice when the "
    "pool is mesh-sharded", labelnames=_POOL_SHARD_LABELS)
_m_bytes_per_token = _metrics.gauge(
    "kv_pool_bytes_per_token", "pool bytes per usable token slot "
    "(bytes_total / capacity_tokens — ~half under int8 KV); same "
    "shard label semantics as kv_pool_bytes_total",
    labelnames=_POOL_SHARD_LABELS)

# Prefix-cache telemetry (round 9 tentpole).
_m_prefix_lookups = _metrics.counter(
    "kv_prefix_cache_lookups_total",
    "attach_prefix calls (one per admitted request when caching is on)",
    labelnames=_POOL_LABEL)
_m_prefix_hits = _metrics.counter(
    "kv_prefix_cache_hits_total",
    "attach_prefix calls that matched at least one cached token",
    labelnames=_POOL_LABEL)
_m_prefix_hit_tokens = _metrics.counter(
    "kv_prefix_cache_hit_tokens_total",
    "prompt tokens served from cached blocks instead of prefill",
    labelnames=_POOL_LABEL)
_m_prefix_lookup_tokens = _metrics.counter(
    "kv_prefix_cache_lookup_tokens_total",
    "prompt tokens eligible for matching (prompt length - 1: the last "
    "token is always recomputed to sample token 0)",
    labelnames=_POOL_LABEL)
_m_prefix_evictions = _metrics.counter(
    "kv_prefix_cache_evictions_total",
    "retained blocks reclaimed (index entries dropped) under pool "
    "pressure", labelnames=_POOL_LABEL)
_m_prefix_cow = _metrics.counter(
    "kv_prefix_cache_cow_copies_total",
    "copy-on-write block copies (a write landed in a shared or "
    "index-claimed block)", labelnames=_POOL_LABEL)

# Host-RAM tier telemetry (long-context serving round).
_m_tier_demotions = _metrics.counter(
    "kv_tier_demotions_total",
    "retained blocks demoted from the device pool into the host tier "
    "(index entries moved, device slot freed)", labelnames=_POOL_LABEL)
_m_tier_promotions = _metrics.counter(
    "kv_tier_promotions_total",
    "tier entries promoted back into device blocks ahead of a prefix "
    "match (prefetch-on-attach)", labelnames=_POOL_LABEL)
_m_tier_bytes = _metrics.counter(
    "kv_tier_bytes_total",
    "host tier traffic in encoded (int8 codes+scales) bytes; "
    "direction='out' = device->host demotion, 'in' = host->device "
    "promotion", labelnames=("pool", "direction"))
_m_tier_hit_tokens = _metrics.counter(
    "kv_tier_hit_tokens_total",
    "prompt tokens served from promoted tier blocks instead of prefill "
    "recompute (counted once, at promotion)", labelnames=_POOL_LABEL)

_pool_ids = itertools.count()

#: parent hash of a sequence's first block (nothing hashes to 0).
ROOT_HASH = 0


class BlockPoolExhausted(RuntimeError):
    """Raised when an allocation needs more free blocks than the pool has
    (after reclaiming every LRU-retained prefix-cache block).

    Carries structured pressure fields (r17) so the reliability layer
    can report and reason about the shortfall without parsing the
    message: `needed` blocks requested, `available` blocks obtainable
    (free + reclaimable) at raise time. Both default to -1 for
    messages raised without them (e.g. injected faults)."""

    def __init__(self, msg, *, needed=-1, available=-1):
        super().__init__(msg)
        self.needed = int(needed)
        self.available = int(available)


def blocks_for(num_tokens: int, block_size: int) -> int:
    """Blocks needed to hold `num_tokens` tokens."""
    return max(0, -(-int(num_tokens) // int(block_size)))


def prefix_block_hash(parent: int, tokens) -> int:
    """Rolling content hash of one block: H(parent_hash, block_tokens).

    blake2b over the 16-byte parent digest + the tokens as int64 LE —
    deterministic, dtype-normalized, and collision-safe in a way
    Python's randomized builtin hash() is not (a collision here would
    serve the wrong K/V)."""
    data = int(parent).to_bytes(16, "little") + \
        np.ascontiguousarray(np.asarray(tokens, np.int64)).tobytes()
    return int.from_bytes(
        hashlib.blake2b(data, digest_size=16).digest(), "little")


def split_heads(a, num_heads):
    """Pool rows with the heads named: [.., H*Dh] -> [.., H, Dh].  A
    scale array [.., H] has no Dh to split off and passes through.
    What leaves the pool (a tier payload, an exported prefix, the
    sequence-parallel seam's layer) names the heads; the pool's own
    arrays keep them side by side (module docstring)."""
    if a.shape[-1] == num_heads:
        return a
    return a.reshape(a.shape[:-1] + (num_heads, -1))


@functools.lru_cache(maxsize=8)
def _copy_block_fn(donate):
    """Jitted whole-block device copy (the CoW kernel): one dynamic
    slice + scatter per array leaf, recompiled per (structure, shape,
    dtype) only. kc/vc may be plain arrays or `QuantizedKV`
    (codes, scales) pytrees — block ids index axis 1 of every leaf, so
    one tree-mapped copy moves codes and scales in lockstep."""
    import jax

    def cp(kc, vc, src, dst):
        return jax.tree.map(lambda a: a.at[:, dst].set(a[:, src]),
                            (kc, vc))

    return jax.jit(cp, donate_argnums=(0, 1) if donate else ())


class PagedKVCache:
    """Block-pool KV cache: fixed-size blocks, per-sequence block tables.

    num_layers/num_heads/head_dim: transformer shape (GPT-2 layout).
    block_size: tokens per block. 128 keeps the Pallas ragged-decode
        kernel's lane alignment on TPU; smaller (8/16) wastes less on CPU
        smokes and short sequences.
    num_blocks: pool size INCLUDING the reserved trash block 0, so the
        usable capacity is (num_blocks - 1) * block_size tokens.
    kv_dtype: None stores K/V in `dtype` (the pre-quantization pool).
        "int8" stores int8 codes plus a parallel per-vector scale
        buffer (kv_quant.QuantizedKV) — ~half the bytes per resident
        token; every block operation (alloc/free/CoW/attach/retain/
        truncate/swap-out) moves scales with their block by
        construction, because both live under the same block index.
        The DISPATCH side must match: pair an int8 pool with
        `PagedDecoder(kv_dtype="int8")` (the decoder checks eagerly).
    name: label for the `kv_pool_*` / `kv_prefix_cache_*` metric series
        (auto-assigned "poolN" when omitted, so concurrent caches never
        alias each other's telemetry).
    tier: optional `kv_tier.HostKVTier` (or True for the default one)
        attached below the pool — cold retained blocks demote to host
        RAM instead of being dropped, and prefix matches promote them
        back. None (default) keeps the pre-tier behaviour exactly.
    state_layout, state_slots: a slot-indexed store beside the pool
        (`nn.decode_blocks.DecoderDescription.cache_layout()`; build
        with `for_description`).  The pool's rows are `num_heads *
        head_dim` wide under the same allocator and block tables: ONE
        array of latent rows (`k_blocks`; `v_blocks` is None), or K rows
        and V rows of `num_heads` K/V heads where the layout says
        `values` (fewer than the model's query heads, where groups of
        them share one).  `state` is the store, {name: [layers, slots +
        1, *shape]} as the layout's `store` lists them (a recurrent
        state "S" in float32 and conv tails for KDA layers; conv and
        value tails alone for CCA layers), indexed by a slot that a
        sequence takes with its first block and gives back in `free`
        (slot 0 is the trash slot).  Where the layout has no pool
        (`pool_layers` 0: every layer keeps its sequence in the store)
        `k_blocks` is an array of no rows ([0, num_blocks, block_size,
        0]), a sequence takes no block however long it grows
        (`blocks_for`), and a table row is [state slot] alone.  A slot is
        not zeroed on the device
        when it changes hands: a program that starts a sequence at
        position 0 starts from zero state whatever the slot holds, and
        until another sequence takes the slot it keeps its last
        holder's state.  `table_array` rows are then
        [state slot | blocks].
    """

    def __init__(self, num_layers, num_heads, head_dim, *, block_size=128,
                 num_blocks=64, dtype=None, kv_dtype=None, name=None,
                 tier=None, state_layout=None, state_slots=0):
        import jax.numpy as jnp

        if state_layout is not None and (kv_dtype is not None
                                         or tier is not None):
            raise ValueError(
                "a cache with a recurrent-state store takes neither "
                "kv_dtype nor tier: its pool and store are dense and "
                "device-resident")

        if num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (block 0 is the "
                             "reserved trash block)")
        if kv_dtype not in (None, "int8"):
            raise ValueError(f"unknown kv_dtype {kv_dtype!r} "
                             "(supported: None, 'int8')")
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.kv_dtype = kv_dtype
        self._name = str(name) if name else f"pool{next(_pool_ids)}"
        dt = jnp.float32 if dtype is None else dtype
        self.dtype = dt
        rows = (self.num_layers, self.num_blocks, self.block_size)
        shape = rows + (self.num_heads * self.head_dim,)
        if kv_dtype == "int8":
            from .kv_quant import QuantizedKV

            # one scale a (token, head) vector: [L, N, BS, H]
            self.k_blocks = QuantizedKV(
                jnp.zeros(shape, jnp.int8),
                jnp.zeros(rows + (self.num_heads,), dt))
            self.v_blocks = QuantizedKV(
                jnp.zeros(shape, jnp.int8),
                jnp.zeros(rows + (self.num_heads,), dt))
        elif state_layout is None:
            self.k_blocks = jnp.zeros(shape, dt)
            self.v_blocks = jnp.zeros(shape, dt)
        else:
            # a pool of rows (latents: `num_heads * head_dim` is the
            # row's width and there is no V; or K rows and V rows) and,
            # beside it, the slot-indexed store of what is not keys and
            # values
            self.k_blocks = jnp.zeros(shape, dt)
            self.v_blocks = jnp.zeros(shape, dt) \
                if state_layout["values"] else None
            self.state = {
                name: jnp.zeros((layers, int(state_slots) + 1)
                                + tuple(slot_shape), dtype or dt)
                for name, (layers, slot_shape, dtype)
                in state_layout["store"].items()}
        if state_layout is None:
            self.state = None
        # the recurrent-state store's slots (slot 0 reserved: trash), each
        # held by one sequence from its first block to its `free`
        self.state_slots = int(state_slots) if state_layout else 0
        self._state_free = list(range(self.state_slots, 0, -1))
        self._state_of: dict[object, int] = {}
        self._peak_state = 0
        # block 0 reserved: free list starts at 1
        self._free = list(range(self.num_blocks - 1, 0, -1))
        self._tables: dict[object, list[int]] = {}
        self._lens: dict[object, int] = {}
        # prefix-cache state: refcounts (tables containing each block),
        # the content index hash -> (block, fill, parent), the reverse
        # block -> entry-hashes map, candidate fills per parent hash
        # (lookup iteration), and the LRU retention list of freed blocks
        # that still hold indexed content.
        self._ref: dict[int, int] = {}
        self._index: dict[int, tuple[int, int, int]] = {}
        self._block_entries: dict[int, set[int]] = {}
        self._child_fills: dict[int, dict[int, int]] = {}
        self._retained: OrderedDict[int, None] = OrderedDict()
        self._shard_count = 1  # device shards (serving_dist sets > 1)
        self._peak_blocks = 0
        self._peak_retained = 0
        self._prefix_lookups = 0
        self._prefix_hits = 0
        self._hit_tokens = 0
        self._lookup_tokens = 0
        self._evictions = 0
        self._cow_copies = 0
        # host-RAM tier (long-context round): None = pre-tier behaviour
        self._tier = None
        #: optional callback(kind, **fields) the engine wires to its
        #: flight recorder / tracing — kind is "demote" or "promote"
        self.on_tier_event = None
        self._tier_demotions = 0
        self._tier_promotions = 0
        self._tier_bytes_out = 0
        self._tier_bytes_in = 0
        self._tier_hit_tokens = 0
        #: resource attribution (ISSUE 17): an
        #: `observability.attribution.ResourceLedger` the engine
        #: attaches BEFORE the first allocation. Every non-free block
        #: then carries exactly one (tenant, rid) owner — assigned
        #: when `_take_blocks` pulls it off the free list, cleared
        #: only when the block returns there — so per-tenant block
        #: counts sum to pool occupancy no matter how prefix sharing,
        #: retention, revival or CoW shuffle the references
        #: (the publisher keeps paying for shared blocks; attachers
        #: are credited prefix savings instead).
        self.ledger = None
        self._seq_owner: dict[object, tuple] = {}   # seq -> (tenant, rid)
        self._block_owner: dict[int, tuple] = {}    # block -> (tenant, rid)
        self._tier_owner: dict[int, tuple] = {}     # hash -> (tenant, bytes)
        if tier is not None:
            self.attach_tier(tier)

    @classmethod
    def for_description(cls, desc, *, block_size, num_blocks, dtype,
                        max_slots):
        """The cache a `nn.decode_blocks.DecoderDescription` needs: a
        latent pool (one "head" as wide as a row), a K/V pool by the
        description's K/V heads, or no pool at all (no layer, no width),
        with a store of `max_slots` slots.  A description of "mla" layers
        alone has an empty store: its slots hold no array and cost no
        byte, one a server slot, so admission goes by blocks alone."""
        layout = desc.cache_layout()
        if not layout["pool_layers"]:
            heads, head_dim = 1, 0
        elif layout["values"]:
            heads = desc.cca.kv_heads
            head_dim = layout["row_width"] // heads
        else:
            heads, head_dim = 1, layout["row_width"]
        return cls(layout["pool_layers"], heads, head_dim,
                   block_size=block_size, num_blocks=num_blocks,
                   dtype=dtype, state_layout=layout, state_slots=max_slots)

    def blocks_for(self, num_tokens):
        """Blocks a sequence of `num_tokens` tokens takes: none where the
        cache has no pool."""
        return blocks_for(num_tokens, self.block_size) \
            if self.num_layers else 0

    # ---- the recurrent-state store's slots ----------------------------
    @property
    def free_state_slots(self):
        """State slots a new sequence can take (0 when there is no
        store: ask `state_slots` first)."""
        return len(self._state_free)

    def reset_state_peak(self):
        """Start `peak_used_slots` again from the slots held now (the
        server's `reset_stats`)."""
        self._peak_state = len(self._state_of)

    def state_slot(self, seq_id):
        """The store slot `seq_id` holds (0, the trash slot, if none)."""
        return self._state_of.get(seq_id, 0)

    def state_slot_holder(self, slot):
        """The sequence that holds store slot `slot` (None if none): with
        `block_table`, where a sequence's pool rows lie."""
        return next((s for s, held in self._state_of.items()
                     if held == slot), None)

    # ---- pool bookkeeping (host-side) ---------------------------------
    @property
    def free_block_count(self):
        return len(self._free)

    @property
    def retained_block_count(self):
        return len(self._retained)

    @property
    def available_block_count(self):
        """Blocks an allocation can obtain: the free list plus the
        LRU-retained blocks it may reclaim — the number admission
        control should reason about. Invariant under tiering: a
        demotion moves a block retained -> free (the sum is
        unchanged), so admission never under-counts when content is
        parked in the host tier — the tiered entries cost no device
        block until a match promotes them back into this sum."""
        return len(self._free) + len(self._retained)

    @property
    def capacity_tokens(self):
        return (self.num_blocks - 1) * self.block_size

    @property
    def pool_bytes_total(self):
        """Device bytes held by the K/V pool arrays (codes + scale
        buffers under int8 — dtype-aware, fixed at construction)."""
        import jax

        return sum(int(a.nbytes) for a in
                   jax.tree.leaves((self.k_blocks, self.v_blocks)))

    @property
    def scale_bytes(self):
        """Bytes of the per-vector scale buffers (0 for a dense pool) —
        the quantization overhead on top of the int8 codes."""
        if self.kv_dtype != "int8":
            return 0
        return int(self.k_blocks.scales.nbytes
                   + self.v_blocks.scales.nbytes)

    @property
    def bytes_per_token(self):
        """Pool bytes per usable token slot (includes the trash block's
        amortized share — the honest per-token HBM cost)."""
        return self.pool_bytes_total / (self.capacity_tokens or 1)

    def set_shard_count(self, n):
        """Record how many device shards the pool arrays are placed
        over (serving_dist): the byte gauges then also emit per-shard
        series. Pure telemetry — the block-table API is shard-blind."""
        n = int(n)
        if n < 1:
            raise ValueError(f"shard count must be >= 1, got {n}")
        self._shard_count = n
        self._push_gauges()

    def stats_kv_dtype(self):
        """The stored element dtype as a stats/dashboard string:
        "int8" for a quantized pool, else the dense dtype name."""
        return self.kv_dtype or np.dtype(self.dtype).name

    def _get_table(self, seq_id, op):
        try:
            return self._tables[seq_id]
        except KeyError:
            raise KeyError(
                f"unknown sequence {seq_id!r} in {op}(): not allocated "
                f"in this cache (live sequences: {len(self._tables)})"
            ) from None

    def set_seq_owner(self, seq_id, tenant, rid=None):
        """Register who pays for `seq_id`'s future allocations
        (attribution, ISSUE 17). The engine calls this at slot install,
        before the first `ensure_many` growth; unowned sequences charge
        the "default" tenant. Cleared by `free`."""
        self._seq_owner[seq_id] = (str(tenant), rid)

    def _ledger_block_freed(self, b):
        """A block re-entered the free list: close out its ownership."""
        own = self._block_owner.pop(b, None)
        if own is not None and self.ledger is not None:
            self.ledger.block_event(own[0], own[1], -1)

    def _ledger_tier_add(self, h, tenant, nbytes):
        if self.ledger is None or h in self._tier_owner:
            return
        self._tier_owner[h] = (tenant, nbytes)
        self.ledger.host_bytes_event(tenant, nbytes)

    def _ledger_tier_drop(self, h):
        own = self._tier_owner.pop(h, None)
        if own is not None and self.ledger is not None:
            self.ledger.host_bytes_event(own[0], -own[1])

    def _take_blocks(self, n, owner=None):
        """Pop `n` blocks off the free list (refcount 1 each),
        reclaiming LRU-retained prefix blocks as needed. Callers must
        pre-check availability when they need all-or-nothing semantics
        (`ensure_many` does). `owner` is the (tenant, rid) pair charged
        for the blocks while they stay off the free list."""
        while len(self._free) < n and self._retained:
            self._reclaim_lru()
        if n > len(self._free):
            _m_alloc_failures.labels(pool=self._name).inc()
            raise BlockPoolExhausted(
                f"need {n} blocks, only {len(self._free)} free "
                f"(pool {self.num_blocks - 1})",
                needed=n, available=len(self._free))
        taken = [self._free.pop() for _ in range(n)]
        for b in taken:
            self._ref[b] = 1
        if self.ledger is not None and taken:
            tenant, rid = owner if owner is not None else ("default", None)
            for b in taken:
                self._block_owner[b] = (tenant, rid)
            self.ledger.block_event(tenant, rid, len(taken))
        used = self.num_blocks - 1 - len(self._free) - len(self._retained)
        self._peak_blocks = max(self._peak_blocks, used)
        return taken

    def _release_block(self, b):
        """Drop one table reference to `b`; at refcount zero the block
        goes to the LRU retention list if the prefix index still names
        it, else back to the free list."""
        left = self._ref.get(b, 0) - 1
        if left > 0:
            self._ref[b] = left
            return
        self._ref.pop(b, None)
        if self._block_entries.get(b):
            self._retained[b] = None
            self._retained.move_to_end(b)
            self._peak_retained = max(self._peak_retained,
                                      len(self._retained))
        else:
            self._free.append(b)
            self._ledger_block_freed(b)

    def _reclaim_lru(self):
        """Evict the least-recently-retained block: drop its index
        entries and return it to the free list. With a host tier
        attached the content is demoted instead of dropped — the
        device slot still frees, but the entries stay promotable."""
        if self._tier is not None:
            self._demote_lru()
            return
        b, _ = self._retained.popitem(last=False)
        for h in list(self._block_entries.get(b, ())):
            self._drop_entry(h)
        self._free.append(b)
        self._ledger_block_freed(b)
        self._evictions += 1
        _m_prefix_evictions.labels(pool=self._name).inc()

    def _register_entry(self, h, block, fill, parent):
        self._index[h] = (block, fill, parent)
        self._block_entries.setdefault(block, set()).add(h)
        fills = self._child_fills.setdefault(parent, {})
        fills[fill] = fills.get(fill, 0) + 1
        if self._tier is not None:
            # move semantics: a hash never lives in both indexes — the
            # freshly written device copy wins over a stale tier copy
            self._tier.drop(h)
            self._ledger_tier_drop(h)

    # ---- host-RAM tier (long-context serving round) -------------------
    def attach_tier(self, tier):
        """Attach a `kv_tier.HostKVTier` below this pool (True builds
        the default tier; None detaches — resident tier content is
        simply forgotten). Returns the attached tier (or None)."""
        from .kv_tier import normalize_kv_tier

        self._tier = normalize_kv_tier(tier)
        if self._tier is None:
            for h in list(self._tier_owner):  # forgotten content is
                self._ledger_tier_drop(h)     # no longer anyone's cost
        self._push_gauges()
        return self._tier

    @property
    def tier(self):
        return self._tier

    def _tier_grab(self, b, fill):
        """Host-side copy of rows [0, fill) of block `b` in the tier
        codec: the pool's native codes+scales for an int8 pool
        (bit-exact round trip), `kv_quant.kv_encode` for a dense one."""
        from .kv_quant import QuantizedKV, kv_encode

        # the tier's payload names the heads: codes [L, fill, H, Dh]
        if self.kv_dtype == "int8":
            def grab(arr):
                return QuantizedKV(
                    split_heads(np.asarray(arr.codes[:, b, :fill]),
                                self.num_heads),
                    np.asarray(arr.scales[:, b, :fill]))
        else:
            def grab(arr):
                codes, scales = kv_encode(
                    split_heads(arr[:, b, :fill], self.num_heads))
                return QuantizedKV(np.asarray(codes),
                                   np.asarray(scales))
        return grab(self.k_blocks), grab(self.v_blocks)

    def _tier_install(self, b, fill, k_pay, v_pay):
        """Write a tier payload into rows [0, fill) of device block
        `b`. The .at[].set dispatches ASYNCHRONOUSLY — this is the
        prefetch: by the time the next jitted dispatch consumes the
        pool arrays, the copy has overlapped with host work."""
        import jax.numpy as jnp

        from .kv_quant import kv_decode

        merged = (self.num_layers, fill, -1)  # [L, fill, H * Dh]
        if self.kv_dtype == "int8":
            def put(arr, pay):
                return type(arr)(
                    arr.codes.at[:, b, :fill].set(
                        jnp.asarray(pay.codes, arr.codes.dtype)
                        .reshape(merged)),
                    arr.scales.at[:, b, :fill].set(
                        jnp.asarray(pay.scales, arr.scales.dtype)))
        else:
            def put(arr, pay):
                rows = kv_decode(jnp.asarray(pay.codes),
                                 jnp.asarray(pay.scales), arr.dtype)
                return arr.at[:, b, :fill].set(rows.reshape(merged))
        self.k_blocks = put(self.k_blocks, k_pay)
        self.v_blocks = put(self.v_blocks, v_pay)

    @staticmethod
    def _payload_bytes(*payloads):
        return sum(int(p.codes.nbytes) + int(p.scales.nbytes)
                   for p in payloads)

    def _demote_lru(self):
        """Demote the LRU retained block into the host tier: every
        index entry on it MOVES to the tier (with an encoded host copy
        of its rows) and the device slot joins the free list."""
        b, _ = self._retained.popitem(last=False)
        owner = self._block_owner.get(b, ("default", None))
        moved = 0
        nbytes = 0
        for h in list(self._block_entries.get(b, ())):
            _blk, fill, parent = self._index[h]
            kp, vp = self._tier_grab(b, fill)
            evicted = self._tier.put(h, fill, parent, kp, vp)
            per = self._payload_bytes(kp, vp)
            nbytes += per
            # the demoting block's owner keeps paying — now in host
            # byte-seconds; a capacity eviction ends the old owner's
            self._ledger_tier_add(h, owner[0], per)
            for old in evicted:
                self._ledger_tier_drop(old)
            self._drop_entry(h)
            moved += 1
        self._free.append(b)
        self._ledger_block_freed(b)
        self._tier_demotions += 1
        self._tier_bytes_out += nbytes
        if _metrics.enabled():
            _m_tier_demotions.labels(pool=self._name).inc()
            _m_tier_bytes.labels(pool=self._name,
                                 direction="out").inc(nbytes)
        cb = self.on_tier_event
        if cb is not None:
            cb("demote", block=b, entries=moved, bytes=nbytes)

    def maybe_demote(self):
        """Watermark-driven demotion sweep: while the free list is
        below `tier.watermark` of the usable pool and retained blocks
        remain, demote the coldest. Called from every release path;
        cheap no-op without a tier. Returns blocks demoted."""
        if self._tier is None or self._tier.watermark <= 0:
            return 0
        low = int(self._tier.watermark * (self.num_blocks - 1))
        n = 0
        while len(self._free) < low and self._retained:
            self._demote_lru()
            n += 1
        if n:
            self._push_gauges()
        return n

    def demote_cold(self, n=1):
        """Explicitly demote up to `n` LRU retained blocks to the tier
        (operator / test hook — the watermark sweep is the automatic
        path). Returns blocks actually demoted."""
        moved = 0
        while (moved < int(n) and self._retained
               and self._tier is not None):
            self._demote_lru()
            moved += 1
        if moved:
            self._push_gauges()
        return moved

    def _promote_entry(self, h):
        """Pull one tier entry back into a device block: allocate,
        decode the payload in, register + park in retention (MRU) so
        the caller's chain walk claims it. Returns the promoted
        payload bytes (0 when the device re-published the hash
        meanwhile and the chain walk just continues), or None when the
        entry is gone or no device block is obtainable."""
        ent = self._tier.get(h)
        if ent is None:
            return None
        if h in self._index:
            # the device re-published the same hash meanwhile — the
            # device copy wins, the tier copy is redundant
            self._tier.drop(h)
            self._ledger_tier_drop(h)
            return 0
        if self.available_block_count < 1:
            return None
        fill, parent, kp, vp = ent
        # the promoted device block belongs to whoever paid for the
        # tier entry (the demoter), not whoever triggered the match
        own = self._tier_owner.get(h)
        b = self._take_blocks(
            1, owner=(own[0], None) if own is not None else None)[0]
        self._tier_install(b, fill, kp, vp)
        self._tier.pop(h)
        self._ledger_tier_drop(h)
        self._register_entry(h, b, fill, parent)
        self._release_block(b)  # refcount 0 + indexed -> retention MRU
        nbytes = self._payload_bytes(kp, vp)
        self._tier_promotions += 1
        self._tier_bytes_in += nbytes
        if _metrics.enabled():
            _m_tier_promotions.labels(pool=self._name).inc()
            _m_tier_bytes.labels(pool=self._name,
                                 direction="in").inc(nbytes)
        cb = self.on_tier_event
        if cb is not None:
            cb("promote", block=b, tokens=fill, bytes=nbytes)
        return nbytes

    def _promote_for(self, ids, max_match, limit_blocks=None,
                     overlapped=False, collect=None):
        """Prefetch-on-match: walk the DEVICE chain along `ids` to its
        end, then continue the walk through the TIER index, promoting
        each tiered entry back into the device pool so the subsequent
        `_match_chain` (and the attach claim on top of it) sees one
        unbroken device chain. Returns tokens promoted.

        The tier half of the walk is TIMED and, when it promoted
        anything, reported as ONE aggregated `tier_promote` callback
        event (blocks/tokens/bytes/dur_s/overlapped) — the serving
        layer turns it into its own trace event so promotion wall time
        never hides inside the admission span (the per-entry `promote`
        events are kept for block-level forensics).  `limit_blocks`
        bounds how many device blocks one walk may consume (the
        prefetch tick's anti-thrash budget); `overlapped=True` marks a
        prefetch-ahead walk riding the async round window; `collect`
        (a list) receives the chain hashes actually promoted."""
        if self._tier is None or not len(self._tier):
            return 0
        n = int(ids.size)
        h = ROOT_HASH
        pos = 0
        # device half: same longest-match walk as _match_chain, but
        # tracking the chain hash so the tier walk continues from it
        while pos < max_match:
            cand = self._child_fills.get(h)
            hit = None
            if cand:
                avail = n - pos
                for f in sorted(cand, reverse=True):
                    if f > avail:
                        continue
                    hh = prefix_block_hash(h, ids[pos:pos + f])
                    if hh in self._index:
                        hit = (hh, f)
                        break
            if hit is None:
                break
            hh, f = hit
            use = min(f, max_match - pos)
            pos += use
            if f < self.block_size or use < f:
                return 0       # partial block ends the chain for good
            h = hh
        promoted_tokens = 0
        blocks = 0
        nbytes = 0
        t0 = time.perf_counter()
        while pos < max_match:
            if limit_blocks is not None and blocks >= int(limit_blocks):
                break
            cand = self._tier.child_fills(h)
            hit = None
            if cand:
                avail = n - pos
                for f in sorted(cand, reverse=True):
                    if f > avail:
                        continue
                    hh = prefix_block_hash(h, ids[pos:pos + f])
                    if self._tier.has(hh):
                        hit = (hh, f)
                        break
            if hit is None:
                break
            hh, f = hit
            nb = self._promote_entry(hh)
            if nb is None:
                break          # pool full — serve what promoted so far
            if nb > 0:
                blocks += 1
                nbytes += nb
                if collect is not None:
                    collect.append(hh)
            use = min(f, max_match - pos)
            promoted_tokens += use
            pos += use
            if f < self.block_size or use < f:
                break
            h = hh
        if blocks:
            cb = self.on_tier_event
            if cb is not None:
                cb("tier_promote", blocks=blocks,
                   tokens=promoted_tokens, bytes=nbytes,
                   dur_s=time.perf_counter() - t0,
                   overlapped=bool(overlapped))
        if promoted_tokens:
            self._tier_hit_tokens += promoted_tokens
            if _metrics.enabled():
                _m_tier_hit_tokens.labels(pool=self._name).inc(
                    promoted_tokens)
            self._push_gauges()
        return promoted_tokens

    def prefetch_promote(self, ids, limit_blocks=None):
        """Tier prefetch-ahead (serving round): promote the tiered
        chain tail for `ids` NOW, while the current round's dispatch
        computes, so a later `attach_prefix` for the same stream finds
        the chain already device-resident and pays no promotion wall
        time.  The `_tier_install` writes dispatch asynchronously —
        host→device copies overlap whatever the device is running.
        `limit_blocks` caps the device blocks one call may consume.
        Returns (hashes, tokens, bytes) of what was actually promoted;
        content-identical to the synchronous attach-time promote (the
        same MOVE-semantics walk), so a prefetch that never lands is
        only a wasted copy, never a wrong one."""
        ids = np.asarray(ids).reshape(-1)
        hashes: list = []
        before = self._tier_bytes_in
        tokens = self._promote_for(
            ids, int(ids.size) - 1, limit_blocks=limit_blocks,
            overlapped=True, collect=hashes)
        return hashes, tokens, self._tier_bytes_in - before

    def device_resident_count(self, hashes):
        """How many of `hashes` are device-index-resident right now —
        the prefetch settlement probe (hit = a prefetched block still
        resident when its session is admitted)."""
        return sum(1 for h in hashes if h in self._index)

    def _drop_entry(self, h):
        block, fill, parent = self._index.pop(h)
        ents = self._block_entries.get(block)
        if ents is not None:
            ents.discard(h)
            if not ents:
                del self._block_entries[block]
        fills = self._child_fills.get(parent)
        if fills is not None:
            left = fills.get(fill, 1) - 1
            if left > 0:
                fills[fill] = left
            else:
                fills.pop(fill, None)
                if not fills:
                    del self._child_fills[parent]

    def _push_gauges(self):
        if not _metrics.enabled():  # keep the hot path one branch
            return
        p = self._name
        used = self.num_blocks - 1 - len(self._free) - len(self._retained)
        held = sum(self._lens.values())
        _m_used_blocks.labels(pool=p, tier="device").set(used)
        _m_free_blocks.labels(pool=p, tier="device").set(len(self._free))
        _m_retained_blocks.labels(pool=p,
                                  tier="device").set(len(self._retained))
        if self._tier is not None:
            t = self._tier
            _m_used_blocks.labels(pool=p, tier="host").set(0)
            _m_free_blocks.labels(pool=p, tier="host").set(
                max(0, t.capacity_blocks - len(t)))
            _m_retained_blocks.labels(pool=p, tier="host").set(len(t))
        _m_sequences.labels(pool=p).set(len(self._tables))
        _m_utilization.labels(pool=p).set(held / (self.capacity_tokens
                                                  or 1))
        _m_block_fill.labels(pool=p).set(
            held / ((used * self.block_size) or 1))
        _m_pool_bytes.labels(pool=p, shard="all").set(
            self.pool_bytes_total)
        _m_bytes_per_token.labels(pool=p, shard="all").set(
            self.bytes_per_token)
        if self._shard_count > 1:
            # per-shard slice: the pool arrays shard evenly over the
            # mesh (heads over tp, blocks over dp), so each device
            # holds 1/n of the bytes — the per-HBM number
            per = self.pool_bytes_total / self._shard_count
            per_tok = self.bytes_per_token / self._shard_count
            for s in range(self._shard_count):
                _m_pool_bytes.labels(pool=p, shard=str(s)).set(per)
                _m_bytes_per_token.labels(pool=p,
                                          shard=str(s)).set(per_tok)

    def allocate(self, seq_id, num_tokens):
        """Start a new sequence holding `num_tokens` tokens; returns its
        block table. Raises BlockPoolExhausted without side effects.
        (Thin wrapper over `ensure_many` — every create/grow path shares
        its bookkeeping so the pool invariants live in one place.)"""
        if seq_id in self._tables:
            raise ValueError(f"sequence {seq_id!r} already allocated")
        self.ensure_many([(seq_id, num_tokens)])
        return list(self._tables[seq_id])

    def ensure(self, seq_id, num_tokens):
        """Grow `seq_id` so positions [0, num_tokens) have backing blocks
        (length is also advanced to num_tokens if it grew)."""
        self._get_table(seq_id, "ensure")  # descriptive unknown-seq error
        self.ensure_many([(seq_id, num_tokens)])
        return list(self._tables[seq_id])

    def ensure_many(self, updates):
        """Bulk multi-sequence allocation: atomically create-or-grow
        several sequences so each covers its requested token count.
        `updates`: iterable of (seq_id, num_tokens). Either every
        sequence ends up covered or — when the pool can't hold the
        TOTAL demand even after reclaiming every retained block —
        BlockPoolExhausted is raised with NO side effects. One call
        serves a whole packed prefill chunk plan
        (inference/serving.py), so a mid-plan exhaustion can never
        leave half the chunk's sequences grown."""
        updates = [(s, int(n)) for s, n in updates]
        need = []
        total = 0
        for seq_id, n in updates:
            grow = self.blocks_for(n) - len(self._tables.get(seq_id, ()))
            need.append(max(0, grow))
            total += max(0, grow)
        if total > len(self._free) + len(self._retained):
            _m_alloc_failures.labels(pool=self._name).inc()
            raise BlockPoolExhausted(
                f"need {total} blocks across {len(updates)} sequences, "
                f"only {len(self._free)} free + {len(self._retained)} "
                f"reclaimable (pool {self.num_blocks - 1})",
                needed=total, available=self.available_block_count)
        if self.state_slots:
            fresh = [s for s, _n in updates if s not in self._tables]
            if len(fresh) > len(self._state_free):
                raise BlockPoolExhausted(
                    f"{len(fresh)} new sequences need a recurrent-state "
                    f"slot each, only {len(self._state_free)} of "
                    f"{self.state_slots} free", needed=len(fresh),
                    available=len(self._state_free))
            for s in fresh:
                self._state_of[s] = self._state_free.pop()
            self._peak_state = max(self._peak_state, len(self._state_of))
        for (seq_id, n), grow in zip(updates, need):
            table = self._tables.setdefault(seq_id, [])
            if grow:
                table.extend(self._take_blocks(
                    grow, owner=self._seq_owner.get(seq_id)))
            self._lens[seq_id] = max(self._lens.get(seq_id, 0), n)
        self.maybe_demote()    # allocation raised pool pressure
        self._push_gauges()

    def append(self, seq_id, n=1):
        """Reserve room for `n` more tokens; returns the (possibly grown)
        block table."""
        return self.ensure(seq_id, self.seq_len(seq_id) + int(n))

    def free(self, seq_id):
        """Release a sequence's blocks (refcount-aware: shared prefix
        blocks stay live for their other referents, indexed blocks park
        in the LRU retention list); returns how many table entries were
        released."""
        table = self._get_table(seq_id, "free")
        del self._tables[seq_id]
        del self._lens[seq_id]
        self._seq_owner.pop(seq_id, None)
        slot = self._state_of.pop(seq_id, None)
        if slot is not None:   # the state goes with the blocks
            self._state_free.append(slot)
        for b in reversed(table):
            self._release_block(b)
        self.maybe_demote()    # retention may have grown past watermark
        self._push_gauges()
        return len(table)

    def truncate_seq(self, seq_id, new_len):
        """Roll a sequence back to `new_len` live tokens — the rollback
        half of speculative decoding (rejected draft positions leave the
        cache) and a general shrink primitive. Tail blocks no longer
        covering any live position are released refcount-aware: shared
        prefix blocks stay live for their other referents, blocks the
        index still names park in the LRU retention list. Rows
        >= new_len inside the kept tail block become dead — masking is
        by length everywhere, and later writes simply overwrite them.

        Safe under prefix sharing/CoW because of two standing
        invariants: `publish_prefix` only ever indexes PROMPT tokens, so
        a sequence's speculative tail rows are never entry-claimed; and
        rows >= an entry's fill are outside the immutable region, so
        rewriting them after a rollback needs no copy. Callers that
        truncate below a published/attached region they intend to
        rewrite must route the next write through `prepare_write` (the
        serving engine never truncates below prompt_len + 1).

        Returns the number of table entries released."""
        table = self._get_table(seq_id, "truncate_seq")
        new_len = int(new_len)
        cur = self._lens[seq_id]
        if new_len < 0 or new_len > cur:
            raise ValueError(
                f"cannot truncate sequence {seq_id!r} to {new_len}: "
                f"live length is {cur} (truncate_seq only rolls back)")
        keep = blocks_for(new_len, self.block_size)
        dropped = table[keep:]
        del table[keep:]
        self._lens[seq_id] = new_len
        for b in reversed(dropped):
            self._release_block(b)
        self.maybe_demote()
        self._push_gauges()
        return len(dropped)

    def seq_len(self, seq_id):
        try:
            return self._lens[seq_id]
        except KeyError:
            raise KeyError(
                f"unknown sequence {seq_id!r} in seq_len(): not "
                f"allocated in this cache") from None

    def block_table(self, seq_id):
        return list(self._get_table(seq_id, "block_table"))

    def blocks_held(self, seq_id):
        """Blocks currently backing seq_id (0 if not yet allocated)."""
        return len(self._tables.get(seq_id, ()))

    def has_seq(self, seq_id):
        """Whether seq_id currently owns a block table (the public form
        of the `seq in cache._tables` probe exception handlers need)."""
        return seq_id in self._tables

    # ---- prefix caching (round 9) -------------------------------------
    def _match_chain(self, ids, max_match):
        """Walk the content index along `ids`: the longest chain of
        cached blocks covering a prefix of ids[:max_match]. Returns
        (blocks, fills, pos) — fills[i] is how many tokens block i
        contributes (== block_size for interior blocks; the final
        block may be a partial-tail entry or capped by max_match,
        either of which ends the chain). READ-ONLY: no refcounts,
        counters or gauges move — `attach_prefix` claims on top of
        this, `match_prefix_len`/`export_prefix` (fleet round) just
        read."""
        matched: list[int] = []
        fills: list[int] = []
        h = ROOT_HASH
        pos = 0
        n = int(ids.size)
        while pos < max_match:
            cand = self._child_fills.get(h)
            if not cand:
                break
            avail = n - pos            # tokens we can hash from here
            hit = None
            for f in sorted(cand, reverse=True):  # longest match first
                if f > avail:
                    continue
                hh = prefix_block_hash(h, ids[pos:pos + f])
                ent = self._index.get(hh)
                if ent is not None:
                    hit = (hh, ent, f)
                    break
            if hit is None:
                break
            hh, (block, _fill, _parent), f = hit
            use = min(f, max_match - pos)
            matched.append(block)
            fills.append(use)
            pos += use
            if f < self.block_size or use < f:
                break                  # partial block ends the chain
            h = hh
        return matched, fills, pos

    def match_prefix_len(self, token_ids):
        """Read-only longest-cached-prefix probe: how many tokens of
        `token_ids` an `attach_prefix` with the same stream would
        serve from cache RIGHT NOW (same len-1 cap — the last token is
        always recomputed), with zero side effects: nothing is
        claimed, no hit/lookup counter moves. The fleet router's
        prefix-aware placement signal (route a request to the replica
        already holding its longest prefix).

        With a host tier attached the probe is no longer free: a chain
        continuing into the tier is PROMOTED first (prefetch-on-match
        — by the time the admission decision lands the blocks are
        device-resident), so the returned length counts tiered
        content too."""
        ids = np.asarray(token_ids).reshape(-1)
        self._promote_for(ids, int(ids.size) - 1)
        return self._match_chain(ids, int(ids.size) - 1)[2]

    def attach_prefix(self, seq_id, token_ids):
        """Content-addressed prefix attach: find the longest chain of
        cached blocks matching `token_ids` and start `seq_id` on them by
        copying table entries (refcount bump — no compute, no device
        work). Returns the number of cached tokens (0 = no match, and
        the sequence is NOT created: the caller's normal allocate path
        applies).

        At most `len(token_ids) - 1` tokens ever match: the final
        prompt token is always left to the prefill dispatch, which
        needs at least one real position to sample token 0 from. The
        match may end mid-block (the index also holds the tail partial
        block of every published prompt) — the claimed rows of that
        block are shared, and the sequence's first write into it goes
        through `prepare_write` (copy-on-write)."""
        if seq_id in self._tables:
            raise ValueError(f"sequence {seq_id!r} already allocated")
        ids = np.asarray(token_ids).reshape(-1)
        n = int(ids.size)
        max_match = n - 1
        self._prefix_lookups += 1
        self._lookup_tokens += max(0, max_match)
        if _metrics.enabled():
            _m_prefix_lookups.labels(pool=self._name).inc()
            _m_prefix_lookup_tokens.labels(pool=self._name).inc(
                max(0, max_match))
        self._promote_for(ids, max_match)  # prefetch tiered chain tail
        matched, _fills, pos = self._match_chain(ids, max_match)
        if pos == 0:
            return 0
        for b in matched:              # claim the chain
            r = self._ref.get(b, 0)
            if r == 0:                 # parked in retention: revive
                self._retained.pop(b, None)
            self._ref[b] = r + 1
        self._tables[seq_id] = matched
        self._lens[seq_id] = pos
        self._prefix_hits += 1
        self._hit_tokens += pos
        if _metrics.enabled():
            _m_prefix_hits.labels(pool=self._name).inc()
            _m_prefix_hit_tokens.labels(pool=self._name).inc(pos)
        self._push_gauges()
        return pos

    def publish_prefix(self, seq_id, token_ids):
        """Index `seq_id`'s blocks under their rolling content hashes so
        later sequences can attach them. Call AFTER the K/V for
        `token_ids` has actually been written to the device arrays
        (i.e. once the prompt is fully prefilled). Full blocks chain;
        the tail partial block (if any) is indexed with its fill.
        Hashes that already exist keep their original block (first
        publisher wins)."""
        table = self._get_table(seq_id, "publish_prefix")
        ids = np.asarray(token_ids).reshape(-1)
        n = int(ids.size)
        if n > self._lens[seq_id]:
            raise ValueError(
                f"cannot publish {n} tokens for sequence {seq_id!r}: "
                f"only {self._lens[seq_id]} are live")
        bs = self.block_size
        h = ROOT_HASH
        nfull = n // bs
        for i in range(nfull):
            hh = prefix_block_hash(h, ids[i * bs:(i + 1) * bs])
            if hh not in self._index:
                self._register_entry(hh, table[i], bs, h)
            h = hh
        fill = n - nfull * bs
        if fill:
            hh = prefix_block_hash(h, ids[nfull * bs:])
            if hh not in self._index:
                self._register_entry(hh, table[nfull], fill, h)

    def prepare_write(self, seq_id, pos):
        """Make the block holding position `pos` exclusively writable
        for `seq_id` before a dispatch writes K/V there. No-op for
        fresh blocks. If the block is shared (refcount > 1) or the
        prefix index claims rows at/after `pos`, the block is COPIED
        on the device and the table entry swapped (copy-on-write) —
        every other referent and the index keep the original. When the
        pool has no spare block and the sequence is the sole referent,
        the blocking index entries are dropped instead and the write
        proceeds in place (no copy needed). Returns True iff a CoW
        copy happened."""
        import jax
        import jax.numpy as jnp

        table = self._get_table(seq_id, "prepare_write")
        bi = int(pos) // self.block_size
        if bi >= len(table):
            return False               # growth region: nothing cached
        block = table[bi]
        row = int(pos) % self.block_size
        shared = self._ref.get(block, 0) > 1
        blocking = [h for h in self._block_entries.get(block, ())
                    if self._index[h][1] > row]
        if not shared and not blocking:
            return False               # exclusive + unclaimed rows
        if self.available_block_count >= 1:
            new = self._take_blocks(
                1, owner=self._seq_owner.get(seq_id))[0]
            fn = _copy_block_fn(jax.default_backend() not in ("cpu",))
            self.k_blocks, self.v_blocks = fn(
                self.k_blocks, self.v_blocks, jnp.int32(block),
                jnp.int32(new))
            table[bi] = new
            self._release_block(block)
            self._cow_copies += 1
            _m_prefix_cow.labels(pool=self._name).inc()
            self._push_gauges()
            return True
        if shared:
            _m_alloc_failures.labels(pool=self._name).inc()
            raise BlockPoolExhausted(
                f"copy-on-write for sequence {seq_id!r} at position "
                f"{pos} needs 1 block, pool exhausted "
                f"(pool {self.num_blocks - 1})", needed=1, available=0)
        for h in blocking:             # sole referent: cede the cache
            self._drop_entry(h)        # entries, write in place
        return False

    def swap_out_seq(self, seq_id, token_ids):
        """Preemption swap-out hook (round 12): publish the sequence's
        LIVE K/V prefix into the content index, then release its blocks.
        `token_ids` is the full known token stream (prompt + generated);
        only the first `seq_len(seq_id)` of them have K/V written, and
        exactly those are indexed — the freed blocks park in the LRU
        retention list instead of being scrubbed, so a later
        `attach_prefix` with the same stream resumes the sequence with
        near-zero recompute (one token) unless pool pressure reclaimed
        the blocks in between. Returns the number of tokens published
        (0 for an empty sequence — nothing to index)."""
        live = self.seq_len(seq_id)
        ids = np.asarray(token_ids).reshape(-1)
        if live > ids.size:
            raise ValueError(
                f"swap_out_seq of {seq_id!r}: {live} live tokens but "
                f"only {ids.size} token ids supplied")
        if live > 0:
            self.publish_prefix(seq_id, ids[:live])
        self.free(seq_id)
        return live

    # ---- cross-pool migration (fleet round) ---------------------------
    def export_prefix(self, token_ids):
        """Serialize the longest cached chain matching `token_ids` for
        migration to ANOTHER pool: host-side numpy copies of the block
        contents (int8 codes + scales travel together under a
        quantized pool) plus per-block fills and the pool layout.
        Returns None when the index covers nothing. The inverse,
        `import_prefix`, re-publishes the chain into a
        layout-identical pool so a later `attach_prefix` there resumes
        the session with zero prefill recompute. Read-only on the
        DEVICE chain — but a chain continuing into the host tier is
        promoted first, so a partially-tiered session migrates whole
        (the payload always carries the longest recoverable chain)."""
        import jax

        ids = np.asarray(token_ids).reshape(-1)
        self._promote_for(ids, int(ids.size))
        blocks, fills, pos = self._match_chain(ids, int(ids.size))
        if pos == 0:
            return None

        def grab(arr, b):  # the payload names the heads
            return jax.tree.map(
                lambda a: split_heads(np.asarray(a[:, b]),
                                      self.num_heads), arr)

        return {
            "tokens": [int(t) for t in ids[:pos]],
            "block_size": self.block_size,
            "kv_dtype": self.kv_dtype,
            "num_layers": self.num_layers,
            "num_heads": self.num_heads,
            "head_dim": self.head_dim,
            "fills": list(fills),
            "k": [grab(self.k_blocks, b) for b in blocks],
            "v": [grab(self.v_blocks, b) for b in blocks],
        }

    def import_prefix(self, payload, owner=None):
        """Install an `export_prefix` payload into THIS pool: allocate
        blocks, write the K/V contents on device, and register the
        chain in the content index exactly as `publish_prefix` would
        have — the imported blocks park in the LRU retention list
        (refcount 0, indexed) until an `attach_prefix` claims them.
        First publisher wins: a chain entry whose hash this pool
        already holds keeps the existing block and the redundant
        import block returns to the free list. Raises
        BlockPoolExhausted when the pool cannot cover the chain (the
        caller falls back to journal-replay resume) and ValueError on
        a layout mismatch. `owner` is the attribution (tenant, rid)
        charged for the imported blocks (migration target side).
        Returns the number of tokens published."""
        import jax

        for field in ("block_size", "kv_dtype", "num_layers",
                      "num_heads", "head_dim"):
            if payload[field] != getattr(self, field):
                raise ValueError(
                    f"import_prefix layout mismatch on {field}: "
                    f"payload has {payload[field]!r}, pool has "
                    f"{getattr(self, field)!r}")
        ids = np.asarray(payload["tokens"], np.int64).reshape(-1)
        fills = [int(f) for f in payload["fills"]]
        if not fills or int(ids.size) != sum(fills):
            raise ValueError(
                f"import_prefix payload inconsistent: {ids.size} "
                f"tokens vs fills {fills}")
        new_blocks = self._take_blocks(len(fills),
                                       owner=owner)  # may raise
        for b, pk, pv in zip(new_blocks, payload["k"], payload["v"]):
            def put(a, p, _b=b):  # [L, BS, H, Dh] back into pool rows
                return a.at[:, _b].set(
                    np.asarray(p).reshape((a.shape[0],) + a.shape[2:]))

            self.k_blocks = jax.tree.map(put, self.k_blocks, pk)
            self.v_blocks = jax.tree.map(put, self.v_blocks, pv)
        h = ROOT_HASH
        pos = 0
        for b, f in zip(new_blocks, fills):
            hh = prefix_block_hash(h, ids[pos:pos + f])
            if hh not in self._index:
                self._register_entry(hh, b, f, h)
            # release the construction refcount: indexed blocks park
            # in retention, an already-published duplicate frees
            # outright (first publisher wins)
            self._release_block(b)
            pos += f
            if f < self.block_size:
                break                  # partial tail ends the chain
            h = hh
        self.maybe_demote()
        self._push_gauges()
        return pos

    def table_array(self, seq_ids, width=None):
        """Dense int32 [len(seq_ids), width] block-table matrix for the
        jitted step; unused entries point at trash block 0. A seq_id of
        None yields an all-trash row (an idle server slot)."""
        rows = [self._tables.get(s, []) if s is not None else []
                for s in seq_ids]
        if width is None:
            width = max((len(r) for r in rows), default=1) or 1
        lead = 1 if self.state_slots else 0   # [state slot | blocks]
        out = np.zeros((len(rows), lead + int(width)), np.int32)
        for i, r in enumerate(rows):
            if len(r) > width:
                raise ValueError(f"block table of {seq_ids[i]!r} "
                                 f"({len(r)}) exceeds width {width}")
            out[i, lead:lead + len(r)] = r
            if lead:
                out[i, 0] = self._state_of.get(seq_ids[i], 0)
        return out

    def swap_arrays(self, k_blocks, v_blocks, state=None):
        """Install the updated device arrays a jitted prefill/step
        returned (the functional write-back half of the cycle); `state`
        is the recurrent-state store of a cache that has one."""
        self.k_blocks = k_blocks
        self.v_blocks = v_blocks
        if self.state is not None:
            self.state = state

    def block_fill(self):
        """Live tokens / allocated block capacity — the
        `stats()["block_fill"]` value without building the full stats
        dict (both serving engines sample it every decode round)."""
        used = self.num_blocks - 1 - len(self._free) - len(self._retained)
        return sum(self._lens.values()) / ((used * self.block_size) or 1)

    def headroom(self):
        """Lightweight capacity view for the pressure sampler (ISSUE
        17): host-side counters only — no device-array touches, safe
        at per-round sampling rates."""
        held = sum(self._lens.values())
        used = self.num_blocks - 1 - len(self._free) - len(self._retained)
        return {
            "num_blocks": self.num_blocks - 1,
            "used_blocks": used,
            "free_blocks": len(self._free),
            "retained_blocks": len(self._retained),
            "available_blocks": len(self._free) + len(self._retained),
            "sequences": len(self._tables),
            "held_tokens": held,
            "utilization": held / (self.capacity_tokens or 1),
        }

    def stats(self):
        used = self.num_blocks - 1 - len(self._free) - len(self._retained)
        held = sum(self._lens.values())
        # {store array: bytes a slot holds over all its layers}
        entries = {name: int(a.nbytes) // a.shape[1]
                   for name, a in (self.state or {}).items()}
        return {
            "block_size": self.block_size,
            "num_blocks": self.num_blocks - 1,  # usable (trash excluded)
            # HBM accounting (quantized-serving round): dtype-aware
            # byte cost of the pool arrays, so the int8 halving shows
            # up in stats and dashboards, not just in config
            "kv_dtype": self.stats_kv_dtype(),
            "pool_bytes_total": self.pool_bytes_total,
            "pool_bytes_per_token": self.bytes_per_token,
            # the heads a pool row holds (K/V heads: fewer than the
            # model's query heads where groups share one; 1 for a latent
            # row) and the bytes a cached token takes over all layers,
            # so that a reader counts K/V traffic without the model
            "kv_heads": self.num_heads,
            "bytes_per_token": self.pool_bytes_total
            // (self.num_blocks * self.block_size),
            # device shards the pool arrays are placed over (1 =
            # unsharded); per-shard bytes are what one HBM must hold
            "shards": self._shard_count,
            "pool_bytes_per_shard": (self.pool_bytes_total
                                     / self._shard_count),
            "scale_bytes": self.scale_bytes,
            "used_blocks": used,
            "free_blocks": len(self._free),
            "retained_blocks": len(self._retained),
            "peak_retained_blocks": self._peak_retained,
            "peak_used_blocks": self._peak_blocks,
            "sequences": len(self._tables),
            "held_tokens": held,
            # fraction of usable pool tokens occupied by live tokens
            # (per-sequence lengths: shared prefix blocks count once
            # per referent, so >1.0 is possible under heavy sharing)
            "utilization": held / (self.capacity_tokens or 1),
            # live tokens per allocated slot (internal fragmentation:
            # 1.0 = every allocated block byte holds a real token;
            # sharing can push it above 1.0)
            "block_fill": held / ((used * self.block_size) or 1),
            "prefix_cache": {
                "index_entries": len(self._index),
                "lookups": self._prefix_lookups,
                "hits": self._prefix_hits,
                "hit_tokens": self._hit_tokens,
                "lookup_tokens": self._lookup_tokens,
                # matched fraction of matchable prompt tokens (the
                # last token of every prompt is never matchable)
                "hit_rate": self._hit_tokens / (self._lookup_tokens
                                                or 1),
                "evictions": self._evictions,
                "cow_copies": self._cow_copies,
            },
            # host-RAM tier block: zeroed-when-disabled, so the schema
            # is identical with and without a tier attached
            "tier": self._tier_stats(),
            # the recurrent-state store (zeros when the cache has none)
            # and what a slot holds, by array, so that a reader counts
            # state traffic without the model
            "state": {"slots": self.state_slots,
                      "used_slots": len(self._state_of),
                      "peak_used_slots": self._peak_state,
                      "bytes_per_slot": sum(entries.values()),
                      "entries": entries},
        }

    def _tier_stats(self):
        from .kv_tier import disabled_tier_stats

        if self._tier is None:
            return disabled_tier_stats()
        s = self._tier.stats()
        return {
            "enabled": True,
            "capacity_blocks": s["capacity_blocks"],
            "tiered_blocks": s["tiered_blocks"],
            "tiered_tokens": s["tiered_tokens"],
            "bytes_resident": s["bytes_resident"],
            "demotions": self._tier_demotions,
            "promotions": self._tier_promotions,
            "evictions": s["evictions"],
            "bytes_out": self._tier_bytes_out,
            "bytes_in": self._tier_bytes_in,
            "hit_tokens": self._tier_hit_tokens,
        }
