"""Posting-source layer: one decode/skip policy for both engines
(DESIGN.md §2.6).

A query term resolves to one of two *sources*:

  DecodedSource — the padded int32 value array (today's behavior): short
                  lists, cache-resident lists, and codecs without a skip
                  index all land here.
  PackedSource  — the compressed list stays packed; intersection gallops
                  over the block-max skip index and decodes only candidate
                  blocks (paper §6.5).  Long skip-capable lists land here.

The choice is made by ``resolve`` from three inputs — the candidate/list
cardinality ratio, the codec family (``bitpack.skip_capable``), and cache
residency — replacing the two divergent inline heuristics the sequential
and batched engines used to carry.  Crucially the skip path *composes* with
the DecodeCache instead of being mutually exclusive with it: short lists
are decoded once and cached, long lists are skip-probed and never pollute
the cache (their decode cost is exactly what the skip index avoids).

``resolve`` also keeps the decoded-ints accounting (``stats`` dict) that
serve.py and bench_engine.py report: every integer materialized from a
compressed payload is counted, so the partial-decode win is visible as a
number, not a belief.

Device residency (DESIGN.md §2.8): ``ResidentPool`` keeps resolved operands
— decoded value rows, bitmap word rows, and (via the layout memo) packed
layout operands — staged on device with explicit ``jax.device_put`` and
LRU eviction accounting, so steady-state batch assembly is pure
index-gathering over resident buffers instead of per-batch decode + pow2
padding + H2D transfer.

Invariants callers rely on:

  * **Residency wins in resolve** — a list already staged in the pool (or
    the DecodeCache) is served decoded even when the ratio policy would
    skip-probe it; fresh decodes are staged so the next batch gathers
    instead of decoding.  Residency never changes *values*, only where a
    row lives, so engines stay byte-identical with and without a pool.
  * **Device pinning (DESIGN.md §2.5/§2.9)** — a pool constructed with
    ``device=`` commits every buffer it stages to that device, and the
    layout memo keeps per-device copies of packed layout operands.  This is
    what places each index shard's working set on its own device in the
    sharded executor (``repro.index.shard``); ``device=None`` keeps today's
    single-device behavior byte for byte.
  * **Host copies are kept** — schedulers read seed values and block-max
    indexes on host; pool entries always carry the numpy copy so no D2H
    sync lands on the query path.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import bitpack
from repro.core import codecs as codec_lib
from repro.core import intersect as its
from repro.core import varint as varint_lib

# Ratio above which a skip-capable list is probed packed instead of decoded
# (the paper's galloping threshold, re-derived on TPU tile geometry — same
# constant the decoded-path dispatcher uses).
SKIP_MIN_RATIO = its.TILED_MAX_RATIO
# Below this many blocks the skip index cannot prune anything worth the
# extra program: decode instead.
SKIP_MIN_BLOCKS = 4

# Bucket floor for the candidate-block-id buffer (pow2-bucketed like every
# other device shape).
CAND_FLOOR = 8


@dataclasses.dataclass
class DecodedSource:
    """Fully decoded posting list: padded int32 values + valid count.

    ``vals`` may live on host (numpy) or device (pool-resident / cached);
    ``vals_np`` is the host copy when one exists for free (fresh decodes,
    pool entries) so schedulers can read values without a D2H sync.
    ``key`` is the (part.uid, tid) identity for pool lookups."""
    vals: jnp.ndarray
    n: int
    vals_np: np.ndarray | None = None
    key: tuple = ()


@dataclasses.dataclass
class PackedSource:
    """Compressed posting list kept packed for skip-aware partial decode."""
    payload: object            # PackedList | PatchedList
    n: int
    maxes_np: np.ndarray       # host copy of the block-max skip index
    key: tuple = ()            # (part.uid, tid) — layout memoization key

    @property
    def mode(self) -> str:
        return self.payload.mode

    @property
    def block_rows(self) -> int:
        return self.payload.block_rows

    @property
    def num_blocks(self) -> int:
        return int(self.payload.widths.shape[0])

    @property
    def num_rows(self) -> int:
        return int(self.payload.flat_words.shape[0])

    @property
    def num_exceptions(self) -> int:
        return int(getattr(self.payload, "exc_pos",
                           np.zeros(0)).shape[0])

    def candidate_block_ids(self, values: np.ndarray) -> np.ndarray:
        """Unique block ids possibly containing any candidate value."""
        return bitpack.candidate_block_ids(self.maxes_np, values)

    def layout(self, k_pad: int, t_pad: int, e_pad: int) -> bitpack.PackedLayout:
        return bitpack.layout_np(self.payload, k_pad, t_pad, e_pad)

    def self_pads(self) -> tuple[int, int, int]:
        """The payload's own pow2 buckets — the canonical memoization pads
        (group buckets are maxima of member self-pads, so a group-sized
        stack slot zero-extends a self-padded layout; see batch.py)."""
        return bitpack.self_pads(self.payload)


def pad_block_ids(blk: np.ndarray, c_pad: int, k_pad: int) -> np.ndarray:
    """Pad a candidate block-id list to the group bucket; pad entries use the
    out-of-range id ``k_pad`` which the device decodes to all-SENTINEL."""
    out = np.full(c_pad, k_pad, np.int32)
    out[: blk.shape[0]] = blk
    return out


# Memoized padded layouts: building a PackedLayout copies the compressed
# words off device and re-pads them, and the sequential probe re-uploads
# the result — per query, per fold, for lists that by definition recur
# (they are the long head terms).  Keyed by ((part.uid, tid), pads) so
# index rebuilds can't serve stale entries; LRU-bounded by total layout
# ints (like the DecodeCache), since each entry pins a whole compressed
# list.  Under megagroup fusion (DESIGN.md §2.10) the arena assembler
# requests layouts at *family-level* pads — the fused key's k/t/e
# ceilings — and the sticky FusionPlan keeps those ceilings monotone, so
# the family key space (and this memo) converges instead of fragmenting
# per batch.
_LAYOUT_CACHE: OrderedDict = OrderedDict()
_LAYOUT_CACHE_BUDGET = 1 << 26      # total ints across cached layouts
_layout_cache_size = 0


def _layout_ints(pads: tuple) -> int:
    k_pad, t_pad, e_pad = pads
    return t_pad * bitpack.LANES + 3 * k_pad + 2 * e_pad


def _layout_entry(src: PackedSource, pads: tuple, stats: dict | None = None):
    global _layout_cache_size
    key = (src.key, pads)
    entry = _LAYOUT_CACHE.get(key)
    if entry is None:
        _bump(stats, "layout_misses")
        entry = {"np": src.layout(*pads), "dev": {}}
        _LAYOUT_CACHE[key] = entry
        _layout_cache_size += _layout_ints(pads)
        while (_layout_cache_size > _LAYOUT_CACHE_BUDGET
               and len(_LAYOUT_CACHE) > 1):
            (_, old_pads), _ = _LAYOUT_CACHE.popitem(last=False)
            _layout_cache_size -= _layout_ints(old_pads)
    else:
        _bump(stats, "layout_hits")
        _LAYOUT_CACHE.move_to_end(key)
    return entry


def cached_layout_np(src: PackedSource, pads: tuple,
                     stats: dict | None = None) -> bitpack.PackedLayout:
    """Memoized host-side padded layout (batch scheduler stacking)."""
    return _layout_entry(src, pads, stats)["np"]


def cached_layout_dev(src: PackedSource, pads: tuple,
                      stats: dict | None = None, device=None) -> tuple:
    """Memoized device-resident layout operands (sequential probe and the
    pool-resident batch stacks): (words, widths, offsets, maxes, exc_pos,
    exc_add) jnp arrays.  ``device`` pins the copy to one device (sharded
    serving keeps one copy per owning shard; None = default placement)."""
    entry = _layout_entry(src, pads, stats)
    dev = entry["dev"].get(device)
    if dev is None:
        lay = entry["np"]
        dev = tuple(jax.device_put(x, device) for x in (
            lay.words, lay.widths, lay.offsets, lay.maxes,
            lay.exc_pos, lay.exc_add))
        entry["dev"][device] = dev
    return dev


def precompute_layouts(parts, stats: dict | None = None) -> int:
    """Build-time projection of every skip-capable list payload onto its
    self-padded PackedLayout, warming the layout memo so serving never
    re-pads on the host (ISSUE 3).  Returns the number of layouts staged."""
    n = 0
    for part in parts:
        for tid, tp in part.terms.items():
            if (tp.kind == "list" and bitpack.skip_capable(tp.payload)
                    and getattr(tp, "skip_ok", True)
                    and int(tp.payload.widths.shape[0]) >= SKIP_MIN_BLOCKS):
                src = PackedSource(tp.payload, tp.n,
                                   maxes_np=np.asarray(tp.payload.maxes),
                                   key=(part.uid, tid))
                cached_layout_np(src, src.self_pads(), stats)
                n += 1
    return n


def decoded_ints_of(payload) -> int:
    """Integers materialized by a full decode of this payload."""
    if isinstance(payload, varint_lib.VarintList):
        return payload.n
    if bitpack.skip_capable(payload):
        return int(payload.widths.shape[0]) * payload.block_rows * bitpack.LANES
    return int(getattr(payload, "padded_n", payload.n))


def decode_padded_np(codec, tp) -> tuple[np.ndarray, int]:
    """Decode one term posting to (pow2-padded int32 numpy vals, count).

    Dispatch is by payload type through the codec registry
    (``codecs.codec_for``) so mixed-codec indexes — the autotuner's output —
    decode without an index-level codec name; the passed ``codec`` is only
    the fallback for payload types the registry does not know."""
    if isinstance(tp.payload, bitpack.PackedList):
        vals = np.asarray(bitpack.decode_bucketed(tp.payload))[: tp.n]
        vals = vals.astype(np.int32)
    elif isinstance(tp.payload, varint_lib.VarintList):
        vals = varint_lib.decode(tp.payload).astype(np.int32)   # tail codec
    else:
        c = codec_lib.codec_for(tp.payload) or codec
        vals = np.asarray(c.decode(tp.payload))[: tp.n].astype(np.int32)
    size = its.pow2_bucket(tp.n)
    return its.pad_to(vals, size), tp.n


def decode_padded(codec, tp) -> tuple[jnp.ndarray, int]:
    """Decode one term posting to (pow2-padded int32 device vals, count)."""
    vals, n = decode_padded_np(codec, tp)
    return jnp.asarray(vals), n


def _bump(stats, key, by=1):
    if stats is not None:
        stats[key] = stats.get(key, 0) + by


# --------------------------------------------------------------------------
# device-resident operand pool (DESIGN.md §2.8)
# --------------------------------------------------------------------------

class RowArena:
    """Same-shape resident rows packed into ONE device matrix, so a group's
    operand assembly is a single ``buffer[idx]`` gather instead of an n-ary
    stack.  Why: jit dispatch costs ~60µs *per argument* on the host
    backend, so stacking hundreds of row references per batch was the
    dominant serving cost — a gather is 2 arguments regardless of row count
    (DESIGN.md §2.8/§2.9).

    Identity rows (sentinel / all-ones / all-zero / pad-layout) occupy the
    first slots so padded and inactive grid positions gather them by
    construction.  The buffer is rebuilt lazily (host ``np.stack`` + one
    ``device_put``) when new rows joined since the last build — steady
    state rebuilds nothing, and the warm-up rebuild cost is absorbed by
    the warm passes every serving/bench loop already runs.  The buffer's
    row count is padded to a pow2 capacity (filler = the identity row, a
    slot id no index ever takes) so its *shape* changes only O(log rows)
    times — the gather program recompiles per buffer shape, not per added
    row.  Rows are keyed by the same (part.uid, tid) identity the pool
    uses.

    Eviction (ISSUE 6): the pool's LRU eviction calls ``evict(key)`` so a
    churned row's slot reverts to the identity row and lands on a free
    list for the next ``slot()`` miss to reuse — without it every arena
    held a device copy of every row *ever* staged, so real device memory
    grew monotonically while the pool's ``resident_ints`` claimed the
    budget held.  ``ints`` reports the *allocated* footprint (the
    high-water row count — a freed slot's memory is only reclaimed when
    the buffer next rebuilds), which is what the pool counts against its
    capacity."""

    def __init__(self, identities: list, device=None):
        self.rows_np: list = list(identities)
        self.slots: dict = {}
        self.device = device
        self.evictions = 0
        self._free: list[int] = []
        self._buf = None

    def slot(self, key, make_np) -> int:
        s = self.slots.get(key)
        if s is None:
            if self._free:
                s = self._free.pop()
                self.rows_np[s] = make_np()
            else:
                s = len(self.rows_np)
                self.rows_np.append(make_np())
            self.slots[key] = s
            self._buf = None
        return s

    def evict(self, key) -> int:
        """Drop one row: its slot reverts to the identity row and is
        reused by the next ``slot()`` miss, so churn stops growing the
        buffer.  Dispatched gathers are unaffected — mutation rebuilds a
        *new* device buffer; in-flight programs keep the old one.
        Returns the ints the slot will stop pinning once reused."""
        s = self.slots.pop(key, None)
        if s is None:
            return 0
        self.rows_np[s] = self.rows_np[0]
        self._free.append(s)
        self.evictions += 1
        return int(np.prod(self.rows_np[0].shape))

    @property
    def ints(self) -> int:
        return len(self.rows_np) * int(np.prod(self.rows_np[0].shape))

    def buffer(self):
        if self._buf is None:
            cap = 1
            while cap < len(self.rows_np):
                cap <<= 1
            rows = self.rows_np + [self.rows_np[0]] * (cap - len(self.rows_np))
            self._buf = jax.device_put(np.stack(rows), self.device)
        return self._buf


def device_capacity_ints(device=None) -> int:
    """Default pool budget: half of the device's memory, in int32s, as
    the backend reports it (``bytes_limit``); 2^26 ints where it reports
    none (the CPU backend).  A budget below the working set makes the LRU
    evict and re-decode rows every batch."""
    dev = device if device is not None else jax.devices()[0]
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    return limit // 8 if limit else 1 << 26


class ResidentPool:
    """Device-resident index operands: decoded value rows and bitmap word
    rows staged once with explicit ``jax.device_put`` and reused by every
    subsequent batch (packed layouts stay resident through the layout memo
    above — same lifecycle, different key space).

    Entries are LRU-evicted against an int budget with explicit accounting
    (``staged_*`` / ``evicted_*`` / ``resident_ints``), because residency is
    a *capacity decision*: a decoded pool the size of the corpus is just an
    uncompressed index.  ``warm`` stages the decode-policy lists up front
    (build-time staging); anything else lands in the pool the first time a
    batch decodes it, so steady state converges to zero host decode either
    way.

    Residency accounting (ISSUE 6 bugfix): everything the pool puts on
    device is counted against ``capacity_ints`` — store entries *and*
    their per-size pad memos (``pad_ints``, dropped and subtracted when
    the entry evicts) *and* the arena / identity-row overhead
    (``overhead_ints``), which the old accounting ignored entirely: a
    churned pool's arenas kept a device copy of every row ever staged, so
    real device memory could exceed the budget without bound while
    ``stats()`` claimed otherwise.  Evicting a store entry now also
    evicts its rows from every arena (slots go to a free list and are
    reused, see ``RowArena.evict``).  ``stats()['device_ints']`` is the
    full device-side footprint; ``resident_ints`` stays the store-entry
    total (``staged_ints - evicted_ints == resident_ints`` remains an
    invariant).

    Each entry keeps the host numpy copy alongside the device buffer: the
    scheduler's block-max skip search reads seed *values* on host, and a
    D2H sync per seed would serialize the very pipeline the pool feeds.

    ``device`` pins every staged buffer to one device — the sharded
    executor (DESIGN.md §2.5/§2.9) gives each index shard a pool pinned to
    its own device so the shard's whole working set lives where its slice
    of the batch executes.  ``device=None`` is the default placement
    (single-device serving, unchanged).
    """

    def __init__(self, capacity_ints: int | None = None, device=None,
                 tag=None):
        self.capacity = (device_capacity_ints(device) if capacity_ints is None
                         else capacity_ints)
        self.device = device
        self.tag = tag                 # generation tag (DESIGN.md §2.14)
        self._store: OrderedDict = OrderedDict()
        self._pad_rows: dict[tuple, jnp.ndarray] = {}
        self._arenas: dict[tuple, RowArena] = {}
        self.hits = 0
        self.misses = 0
        self.staged_lists = 0
        self.staged_ints = 0
        self.evicted_lists = 0
        self.evicted_ints = 0
        self.resident_ints = 0
        self.pad_ints = 0              # current pad-memo ints (⊂ resident)

    # -- staging -----------------------------------------------------------

    def overhead_ints(self) -> int:
        """Device ints the pool holds *outside* the LRU store: identity
        rows and the row arenas (allocated footprint — see RowArena)."""
        return (sum(int(r.size) for r in self._pad_rows.values())
                + sum(a.ints for a in self._arenas.values()))

    def device_ints(self) -> int:
        """The pool's full device-side footprint — what ``capacity_ints``
        actually bounds (the store alone under-counts by the arena copies
        of every resident row)."""
        return self.resident_ints + self.overhead_ints()

    def _evict(self):
        while (self.device_ints() > self.capacity
               and len(self._store) > 1):
            key, old = self._store.popitem(last=False)
            freed = old["ints"] + old["pad_ints"]
            self.evicted_lists += 1
            self.evicted_ints += freed
            self.resident_ints -= freed
            self.pad_ints -= old["pad_ints"]
            old["pads"].clear()            # drop the pad memos with the entry
            for arena in self._arenas.values():
                arena.evict(key)           # free the arena copies for reuse

    def stage(self, key, vals_np: np.ndarray, n: int,
              dev: jnp.ndarray | None = None):
        """Stage one padded decoded list; ``dev`` reuses an already-staged
        device buffer instead of a second H2D transfer (re-pinned if this
        pool is bound to a device and the buffer lives elsewhere)."""
        if key in self._store:
            self._store.move_to_end(key)
            return self._store[key]
        if dev is None:
            dev = jax.device_put(vals_np, self.device)
        elif self.device is not None and self.device not in dev.devices():
            dev = jax.device_put(dev, self.device)
        entry = {"dev": dev, "np": vals_np, "n": n,
                 "pads": {}, "ints": int(vals_np.shape[0]), "pad_ints": 0}
        self._store[key] = entry
        self.staged_lists += 1
        self.staged_ints += entry["ints"]
        self.resident_ints += entry["ints"]
        self._evict()
        return entry

    def stage_bitmap(self, key, words_np: np.ndarray,
                     dev: jnp.ndarray | None = None) -> jnp.ndarray:
        """Stage one bitmap term's word row (key should carry a 'bm' tag to
        keep it disjoint from decoded-list keys).  ``dev`` reuses an
        already-staged device buffer (same contract as ``stage``)."""
        entry = self._store.get(key)
        if entry is None:
            if dev is None:
                dev = jax.device_put(words_np, self.device)
            elif self.device is not None and self.device not in dev.devices():
                dev = jax.device_put(dev, self.device)
            entry = {"dev": dev,
                     "np": words_np,
                     "n": int(words_np.shape[0]), "pads": {},
                     "ints": int(words_np.shape[0]), "pad_ints": 0}
            self._store[key] = entry
            self.staged_lists += 1
            self.staged_ints += entry["ints"]
            self.resident_ints += entry["ints"]
            self._evict()
        else:
            self._store.move_to_end(key)
        return entry["dev"]

    # -- lookup ------------------------------------------------------------

    def get(self, key):
        """(device vals, host vals, n) or None — counts hit/miss."""
        entry = self._store.get(key)
        if entry is None:
            self.misses += 1
            return None
        self.hits += 1
        self._store.move_to_end(key)
        return entry["dev"], entry["np"], entry["n"]

    def __contains__(self, key) -> bool:
        return key in self._store        # residency peek: no counters

    def padded(self, src: DecodedSource, size: int) -> jnp.ndarray:
        """Device row of ``src`` SENTINEL-padded to ``size`` (a group's fold
        bucket).  Memoized per (entry, size); survives eviction races by
        falling back to an eager device pad of the source's own buffer."""
        base = src.vals
        if base.shape[0] == size:
            return base
        entry = self._store.get(src.key) if src.key else None
        if entry is not None and entry["dev"] is base:
            dev = entry["pads"].get(size)
            if dev is None:
                dev = jax.device_put(its.pad_to(entry["np"], size),
                                     self.device)
                entry["pads"][size] = dev
                entry["pad_ints"] += size
                self.staged_ints += size
                self.resident_ints += size
                self.pad_ints += size
                self._evict()
            return dev
        return jnp.concatenate(
            [base, jnp.full((size - base.shape[0],), its.SENTINEL,
                            jnp.int32)])

    def sentinel_row(self, size: int) -> jnp.ndarray:
        """All-SENTINEL device row (inactive fold / padded batch slots)."""
        row = self._pad_rows.get(("sent", size))
        if row is None:
            row = jax.device_put(np.full(size, its.SENTINEL, np.int32),
                                 self.device)
            self._pad_rows[("sent", size)] = row
        return row

    def ones_row(self, words: int) -> jnp.ndarray:
        """All-ones bitmap row — the probe/AND identity."""
        row = self._pad_rows.get(("ones", words))
        if row is None:
            row = jax.device_put(np.full(words, 0xFFFFFFFF, np.uint32),
                                 self.device)
            self._pad_rows[("ones", words)] = row
        return row

    def zeros_row(self, words: int) -> jnp.ndarray:
        """All-zero bitmap row — padded batch slots (popcount 0)."""
        row = self._pad_rows.get(("zero", words))
        if row is None:
            row = jax.device_put(np.zeros(words, np.uint32), self.device)
            self._pad_rows[("zero", words)] = row
        return row

    # -- arenas (gather-based group assembly; DESIGN.md §2.8/§2.9) ---------

    # identity-slot layout shared with the batch assembler:
    #   fold arenas:   slot 0 = all-SENTINEL row
    #   bitmap arenas: slot 0 = all-ones (probe/AND identity),
    #                  slot 1 = all-zero (padded batch rows, popcount 0)
    FOLD_PAD_SLOT = 0
    BM_ONES_SLOT = 0
    BM_ZERO_SLOT = 1

    def fold_arena(self, size: int) -> RowArena:
        """Arena of SENTINEL-padded int32 value rows of length ``size``."""
        a = self._arenas.get(("fold", size))
        if a is None:
            a = RowArena([np.full(size, its.SENTINEL, np.int32)],
                         device=self.device)
            self._arenas[("fold", size)] = a
        return a

    def bitmap_arena(self, words: int) -> RowArena:
        a = self._arenas.get(("bm", words))
        if a is None:
            a = RowArena([np.full(words, 0xFFFFFFFF, np.uint32),
                          np.zeros(words, np.uint32)], device=self.device)
            self._arenas[("bm", words)] = a
        return a

    def layout_arena(self, pads: tuple, op: int) -> RowArena:
        """Arena of packed-layout operand ``op`` (word rows, widths,
        offsets, maxes, exc_pos, exc_add — the _compose_pk order minus the
        candidate block ids) at group pads — family-level ceilings when
        the fused scheduler is driving (one arena set per family, not per
        scheduled signature); slot 0 is the all-pad layout whose blocks
        are never candidates."""
        a = self._arenas.get(("lay", pads, op))
        if a is None:
            k_pad, t_pad, e_pad = pads
            idn = (np.zeros((t_pad, bitpack.LANES), np.uint32),
                   np.zeros(k_pad, np.int32),
                   np.zeros(k_pad, np.int32),
                   np.zeros(k_pad, np.uint32),
                   np.full(e_pad, -1, np.int32),
                   np.zeros(e_pad, np.uint32))[op]
            a = RowArena([idn], device=self.device)
            self._arenas[("lay", pads, op)] = a
        return a

    def arena_stats(self) -> dict:
        return {"arenas": len(self._arenas),
                "arena_ints": sum(a.ints for a in self._arenas.values()),
                "arena_rows": sum(len(a.slots)
                                  for a in self._arenas.values()),
                "arena_evictions": sum(a.evictions
                                       for a in self._arenas.values())}

    # -- lifecycle ---------------------------------------------------------

    def carry_from(self, other: "ResidentPool",
                   part_uids: set | None = None) -> int:
        """Generation-swap residency reuse (DESIGN.md §2.14): adopt another
        pool's staged entries — decoded rows and bitmap rows — around
        their existing device buffers, so surviving segments pay neither a
        re-decode nor a second H2D transfer when a new generation's pool
        is built next to the old one.  ``part_uids`` restricts the carry
        to entries whose part survived the swap (None = carry all).
        Returns the number of entries carried; LRU accounting counts them
        as freshly staged in this pool."""
        carried = 0
        for key, e in list(other._store.items()):
            if part_uids is not None:
                uid = key[1] if (key and key[0] == "bm") else key[0]
                if uid not in part_uids:
                    continue
            if key in self._store:
                continue
            if key and key[0] == "bm":
                self.stage_bitmap(key, e["np"], dev=e["dev"])
            else:
                self.stage(key, e["np"], e["n"], dev=e["dev"])
            carried += 1
        return carried

    def warm(self, index, stats: dict | None = None) -> dict:
        """Stage the whole index per the resolve policy: bitmaps and
        decode-policy lists go resident decoded; skip-capable long lists
        stay compressed (their memory story *is* the skip index) and only
        warm their self-padded layout projection.  Already-resident
        entries (e.g. carried across a generation swap) skip the decode
        entirely."""
        codec = codec_lib.get_codec(index.codec_name)
        for part in index.parts:
            for tid, tp in part.terms.items():
                if tp.kind == "bitmap":
                    self.stage_bitmap(("bm", part.uid, tid),
                                      np.asarray(tp.payload))
                elif tp.kind == "list":
                    if (part.uid, tid) in self._store:
                        self._store.move_to_end((part.uid, tid))
                        continue
                    if (bitpack.skip_capable(tp.payload) and
                            getattr(tp, "skip_ok", True) and
                            int(tp.payload.widths.shape[0])
                            >= SKIP_MIN_BLOCKS):
                        continue                 # serves packed: stay compressed
                    vals, n = decode_padded_np(codec, tp)
                    _bump(stats, "decoded_ints",
                          decoded_ints_of(tp.payload))
                    self.stage((part.uid, tid), vals, n)
        precompute_layouts(index.parts, stats)
        return self.stats()

    def stats(self) -> dict:
        return {"tag": self.tag,
                "resident_lists": len(self._store),
                "resident_ints": self.resident_ints,
                "staged_lists": self.staged_lists,
                "staged_ints": self.staged_ints,
                "evicted_lists": self.evicted_lists,
                "evicted_ints": self.evicted_ints,
                "pad_ints": self.pad_ints,
                "overhead_ints": self.overhead_ints(),
                "device_ints": self.device_ints(),
                "hits": self.hits, "misses": self.misses,
                **self.arena_stats()}


def resolve(part, tid: int, tp, codec, cache=None, r_count: int | None = None,
            skip: bool = True, stats: dict | None = None,
            pool: ResidentPool | None = None):
    """Resolve one term posting to a DecodedSource or a PackedSource.

    r_count: current (or scheduled) candidate cardinality — None means this
    term *is* the candidate seed and must decode.  skip=False forces the
    decoded path everywhere (the pre-skip engine behavior, kept for A/B
    benchmarking).  pool: optional ResidentPool — residency wins like cache
    residency does (an already-staged list is free to reuse), and fresh
    decodes are staged so the next batch gathers instead of decoding.
    """
    key = (part.uid, tid)
    want_skip = (skip and r_count is not None
                 and bitpack.skip_capable(tp.payload)
                 and getattr(tp, "skip_ok", True)
                 and tp.n / max(r_count, 1) > SKIP_MIN_RATIO
                 and int(tp.payload.widths.shape[0]) >= SKIP_MIN_BLOCKS)
    if want_skip:
        # residency wins: an already-decoded list is free to reuse
        if cache is not None and key in cache:
            vals, n = cache.get(key)
            return DecodedSource(vals, n, key=key)
        if pool is not None and key in pool:
            dev, vals_np, n = pool.get(key)
            _bump(stats, "resident_hits")
            return DecodedSource(dev, n, vals_np=vals_np, key=key)
        return PackedSource(tp.payload, tp.n,
                            maxes_np=np.asarray(tp.payload.maxes), key=key)
    if pool is not None:
        hit = pool.get(key)
        if hit is not None:
            _bump(stats, "resident_hits")
            return DecodedSource(hit[0], hit[2], vals_np=hit[1], key=key)
    if cache is not None:
        hit = cache.get(key)
        if hit is not None:
            if pool is not None:          # promote: next batch gathers
                pool.stage(key, np.asarray(hit[0]), hit[1], dev=hit[0])
            return DecodedSource(hit[0], hit[1], key=key)
    vals_np, n = decode_padded_np(codec, tp)
    _bump(stats, "decoded_ints", decoded_ints_of(tp.payload))
    _bump(stats, "decoded_lists")
    if pool is not None:
        # stage first so the buffer lands on the pool's device (sharded
        # pools are device-pinned) and the source serves the staged copy
        vals = pool.stage(key, vals_np, n)["dev"]
    else:
        vals = jnp.asarray(vals_np)
    if cache is not None:
        cache.put(key, vals, n)
    return DecodedSource(vals, n, vals_np=vals_np, key=key)
