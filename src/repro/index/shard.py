"""Sharded query fan-out: index parts on data-parallel devices
(DESIGN.md §2.5, §2.9).

The paper partitions posting lists into cache-sized doc-id ranges and
intersects per partition; at cluster scale those partitions *are* the unit
of data parallelism.  This module maps index parts 1:1 (contiguously, when
counts differ) onto shards, pins each shard's ``ResidentPool`` working set
to its own device, fans every query batch out to all shards, and
concatenates per-part hits in part order — byte-identical to the
single-device engine.

Execution model — shard along the batch axis, not the program:

  The batched scheduler's device programs are row-independent (every
  (query, part) work item is one row of a vmapped program; the only scanned
  axis is the fold axis J, which is not sharded).  So the sharded executor
  does NOT build new per-shard programs: it assembles each shard's rows on
  that shard's device, glues the slices into one global operand with
  ``jax.make_array_from_single_device_arrays`` under a plain
  ``NamedSharding(Mesh(devices, ('data',)), P('data', ...))``, and calls the
  *same* jitted group program the single-device path uses.  XLA's SPMD
  partitioner splits the row axis across devices with zero collectives —
  each device intersects exactly its shard's rows, concurrently.  Group
  keys, bucketing, and per-item math are untouched, which is what makes
  sharded == sequential a structural identity rather than a numerical
  accident (``tests/test_shard.py`` locks it in).

  Meshes here are plain ``Mesh`` objects: nothing in this dataflow needs
  explicit axis types (every axis is Auto).  The whole layer runs under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` for tests/CI and
  on real device fleets unchanged.

  More shards than devices is allowed (shards fold onto devices
  contiguously, ``n_shards %% n_devices == 0``), which keeps the shard
  count a *logical* choice: the same 4-shard index serves on 1, 2, or 4
  devices, and the differential tests run on whatever the host offers.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import trace
from repro.index import batch as batch_lib
from repro.index import pipeline as pipe_lib
from repro.index import source
from repro.index.builder import HybridIndex
from repro.index.engine import QueryResult


@dataclasses.dataclass
class PartPools:
    """Per-part pool routing: ``schedule`` resolves each (query, part) item
    through the pool of the shard that owns the part, so staged buffers land
    on (and are gathered from) the owning shard's device."""
    pools: list
    part_shard: list

    def for_part(self, pi: int):
        return self.pools[self.part_shard[pi]]


@dataclasses.dataclass
class ShardedIndex:
    """A HybridIndex plus its shard topology: part→shard map, shard→device
    placement, and one device-pinned ResidentPool per shard."""
    index: HybridIndex
    n_shards: int
    mesh: object                      # 1-D ('data',) Mesh
    part_shard: list                  # part ordinal -> shard id (contiguous)
    placement: list                   # shard id -> jax Device
    pools: list                       # shard id -> source.ResidentPool

    @property
    def pool_map(self) -> PartPools:
        return PartPools(self.pools, self.part_shard)

    @property
    def devices(self) -> list:
        return list(self.mesh.devices.flat)

    def warm(self, stats: dict | None = None) -> dict:
        """Stage every shard's working set on its own device (build-time
        staging, per the resolve policy — skip-served lists stay packed)."""
        for sid, pool in enumerate(self.pools):
            parts = [p for p, s in zip(self.index.parts, self.part_shard)
                     if s == sid]
            view = HybridIndex(n_docs=self.index.n_docs, B=self.index.B,
                               codec_name=self.index.codec_name, parts=parts)
            pool.warm(view, stats)
        return self.stats()

    def stats(self) -> dict:
        """Placement-map accounting: which parts and how many resident ints
        live on which device, per shard."""
        shards = []
        for sid, pool in enumerate(self.pools):
            ps = pool.stats()
            shards.append({
                "shard": sid,
                "device": str(self.placement[sid]),
                "parts": [p for p, s in enumerate(self.part_shard)
                          if s == sid],
                **ps,
            })
        return {"n_shards": self.n_shards,
                "n_devices": len(self.devices),
                "shards": shards}


def shard_index(index: HybridIndex, n_shards: int, devices=None,
                capacity_ints: int | None = None, warm: bool = True
                ) -> ShardedIndex:
    """Place an index's parts onto ``n_shards`` data-parallel shards.

    Parts map contiguously onto shards (1:1 when ``n_parts == n_shards``,
    the intended production shape); shards map contiguously onto the mesh
    devices.  With fewer devices than shards, consecutive shards share a
    device — the dataflow is identical, only the physical parallelism
    shrinks — so correctness never depends on the host's device count.
    """
    from repro.launch.mesh import make_index_mesh
    assert n_shards >= 1, n_shards
    if devices is None:
        ndev = len(jax.devices())
        # widest mesh that divides the shard count evenly
        width = max(d for d in range(1, min(n_shards, ndev) + 1)
                    if n_shards % d == 0)
        mesh = make_index_mesh(width)
    else:
        # explicit placement: mesh over exactly these devices, in order
        mesh = jax.sharding.Mesh(np.array(devices), ("data",))
    devs = list(mesh.devices.flat)
    assert n_shards % len(devs) == 0, (n_shards, len(devs))
    per_dev = n_shards // len(devs)
    placement = [devs[s // per_dev] for s in range(n_shards)]
    n_parts = len(index.parts)
    part_shard = [min(p * n_shards // max(n_parts, 1), n_shards - 1)
                  for p in range(n_parts)]
    pools = [source.ResidentPool(capacity_ints=capacity_ints, device=d)
             for d in placement]
    sharded = ShardedIndex(index=index, n_shards=n_shards, mesh=mesh,
                           part_shard=part_shard, placement=placement,
                           pools=pools)
    if warm:
        sharded.warm()
    return sharded


# --------------------------------------------------------------------------
# shard-axis glue
# --------------------------------------------------------------------------

def _spec(ndim: int, axis: int) -> P:
    return P(*(["data" if i == axis else None for i in range(ndim)]))


def _glue(sharded: ShardedIndex, slices: list, axis: int):
    """Glue per-shard device slices into one global array sharded along
    ``axis``.  Single-device meshes concatenate eagerly (everything already
    lives there); multi-device meshes zero-copy assemble the committed
    slices with ``make_array_from_single_device_arrays``."""
    devs = sharded.devices
    if len(devs) == 1:
        return jnp.concatenate(slices, axis=axis)
    per_dev = len(slices) // len(devs)
    dev_slices = [slices[d * per_dev] if per_dev == 1
                  else jnp.concatenate(
                      slices[d * per_dev: (d + 1) * per_dev], axis=axis)
                  for d in range(len(devs))]
    # commit stragglers (zero-row fold stacks are built uncommitted)
    dev_slices = [jax.device_put(s, d) for s, d in zip(dev_slices, devs)]
    shape = list(dev_slices[0].shape)
    shape[axis] *= len(devs)
    sharding = NamedSharding(sharded.mesh, _spec(len(shape), axis))
    return jax.make_array_from_single_device_arrays(
        tuple(shape), sharding, dev_slices)


def _put_host(sharded: ShardedIndex, arr: np.ndarray, axis: int | None):
    """Upload one host-side operand (active masks, candidate block ids,
    the fold megakernels' row extents) sharded along ``axis`` — each
    device receives only its slice; None replicates it (the probe
    extents)."""
    if len(sharded.devices) == 1:
        return jnp.asarray(arr)
    sharding = NamedSharding(sharded.mesh, _spec(arr.ndim, axis))
    return jax.device_put(arr, sharding)


# --------------------------------------------------------------------------
# sharded launch (the fan-out) — collect is batch_lib.collect_batch
# --------------------------------------------------------------------------

def _flat_items(per_shard: list, Bq: int) -> list:
    """Collect-order item list of one sharded chunk: shard-contiguous rows,
    None in the per-shard padding slots (skipped by ``collect_batch``)."""
    return [it for sub in per_shard
            for it in list(sub) + [None] * (Bq - len(sub))]


def _launch_svs_sharded(sharded: ShardedIndex, key, per_shard: list,
                        backend: str, stats: dict | None):
    """One device program covering all shards' items of one group chunk:
    rows are laid out shard-contiguously ((shard, slot) flattened), operands
    assembled per shard on the owning device and glued along the row axis.
    Fused megagroup keys pin the arity ceilings (batch.fuse_groups), so
    every shard's slice assembles at the same fused shapes.  Returns
    (flat item list with None pads, vals, counts)."""
    S = sharded.n_shards
    all_items = [it for sub in per_shard for it in sub]
    Bq = batch_lib._bucket_rows(max(len(sub) for sub in per_shard))
    if key.fused:
        J, Jb, Jp = key.fused
    else:
        J = max((len(it.folds) for it in all_items), default=0)
        Jb = max((batch_lib._n_bitmaps(it) for it in all_items), default=0)
        Jp = (max((len(it.psrc) for it in all_items), default=0)
              if key.packed is not None else 0)
    Rs, Fs, As, Pk, Ws = [], [], [], [], []
    for sid in range(S):
        R, F, act, pkparts, W, _, _, _ = batch_lib._assemble_svs(
            key, per_shard[sid], sharded.pools[sid],
            bp=Bq, j=J, jb=Jb, jp=Jp)
        Rs.append(R)
        Fs.append(F)
        As.append(act)
        Pk.append(pkparts)
        Ws.append(W)
    R = _glue(sharded, Rs, axis=0)                      # (S·Bq, M)
    F = _glue(sharded, Fs, axis=1)                      # (J, S·Bq, N)
    active = _put_host(sharded, np.concatenate(As, axis=1), axis=1)
    pk = pk_active = None
    mode, rows = "d1", 32
    if key.packed is not None:
        rows, mode = key.packed[4], key.packed[5]
        # actual partial-decode volume at the launching key's c_pad (see
        # batch._launch_svs_group — fusion may have raised the bucket)
        source._bump(stats, "decoded_ints",
                     sum(len(it.psrc) for it in all_items)
                     * key.packed[2] * rows * 128)
        stacked = [_glue(sharded, [p[0][o] for p in Pk], axis=1)
                   for o in range(6)]
        PBk = _put_host(sharded,
                        np.concatenate([p[1] for p in Pk], axis=1), axis=1)
        pk = batch_lib._compose_pk(stacked, PBk)
        pk_active = _put_host(
            sharded, np.concatenate([p[2] for p in Pk], axis=1), axis=1)
    W = _glue(sharded, Ws, axis=1) if Jb else None      # (Jb, S·Bq, W)
    if stats is not None:
        stats.setdefault("signatures", set()).add(
            ("svs-sharded", key, S, Bq, J, Jb))
    # same interpret-mode occupancy guard as batch._launch_svs_group, at
    # the sharded grid's S·Bq batch rows
    backend = batch_lib._effective_backend(key, all_items, backend, stats,
                                           bp=S * Bq)
    batch_lib.count_folds(stats, all_items, backend, R, F, pk, rows)
    # one extent per bitmap slot over every shard's rows, replicated: each
    # device loops as far as the longest seed anywhere, with no reduction
    # across rows on the device
    chunks, c = batch_lib.probe_chunks(all_items, Jb, key.m_bucket)
    batch_lib.count_probes(stats, all_items, chunks, c, S * Bq)
    probe_n = _put_host(sharded, chunks, axis=None) if Jb else None
    # the fold megakernels' row extents split on rows like R: each device
    # reads its own rows' extents
    flat = _flat_items(per_shard, Bq)
    tiles = _put_host(sharded, batch_lib.seed_tiles(flat, S * Bq), axis=0)
    with trace.span("dispatch"):
        vals, counts = batch_lib._svs_program(
            R, F, active, pk, pk_active, W, probe_n, tiles, key.algo,
            backend, mode, rows, probe_chunk=c,
            mesh=sharded.mesh if len(sharded.devices) > 1 else None)
    return flat, vals, counts


def _launch_bitmap_sharded(sharded: ShardedIndex, key, per_shard: list,
                           stats: dict | None):
    S = sharded.n_shards
    all_items = [it for sub in per_shard for it in sub]
    Bq = batch_lib._bucket_rows(max(len(sub) for sub in per_shard))
    J = (key.fused[0] if key.fused else
         max((batch_lib._n_bitmaps(it) for it in all_items), default=1))
    Ws = [batch_lib._assemble_bitmap(key, per_shard[sid],
                                     sharded.pools[sid], bp=Bq, j=J)[0]
          for sid in range(S)]
    words = _glue(sharded, Ws, axis=0)                  # (S·Bq, J, W)
    if stats is not None:
        stats.setdefault("signatures", set()).add(
            ("bm-sharded", key, S, Bq, J))
    with trace.span("dispatch"):
        vals, counts = batch_lib._bitmap_and_program(words)
    return _flat_items(per_shard, Bq), vals, counts


def launch_groups_sharded(sharded: ShardedIndex, groups, *, n_queries: int,
                          backend: str = "jax", max_results: int = 1 << 16,
                          max_group_size: int = batch_lib.MAX_GROUP_SIZE,
                          stats: dict | None = None
                          ) -> batch_lib.PendingBatch:
    """Dispatch every group chunk as one SPMD program across the shard
    devices, without materializing results (the fan-out half; the existing
    ``batch.collect_batch`` is the concatenate half — item part ordinals
    order per-query results exactly as the single-device engine does).
    With fused megagroups the per-batch dispatch collapse multiplies by
    the shard count: one program per family covers *all* shards' rows.
    Each chunk's ``assemble`` span holds its ``dispatch`` span: assembly
    and glue are its self time."""
    launched = []
    n_dispatches = 0
    c0 = batch_lib._compile_count() if stats is not None else 0
    for key, items in groups.items():
        per = [[] for _ in range(sharded.n_shards)]
        for it in items:
            per[sharded.part_shard[it.pi]].append(it)
        # lockstep chunking: the int budget bounds *per-device* operand
        # rows, so chunk by the widest shard's slice
        step = batch_lib._chunk_size(key, items, max_group_size)
        width = max(len(sub) for sub in per)
        for lo in range(0, max(width, 1), step):
            sub = [s[lo: lo + step] for s in per]
            with trace.span("assemble"):
                if key.kind == "bitmap":
                    flat, vals, counts = _launch_bitmap_sharded(
                        sharded, key, sub, stats)
                else:
                    flat, vals, counts = _launch_svs_sharded(
                        sharded, key, sub, backend, stats)
            launched.append((key, flat, vals, counts))
            n_dispatches += 1
    batch_lib.accumulate_launch_stats(stats, groups, n_dispatches)
    if stats is not None:
        stats["n_compiles"] = (stats.get("n_compiles", 0)
                               + batch_lib._compile_count() - c0)
    return batch_lib.PendingBatch(n_queries=n_queries,
                                  max_results=max_results,
                                  launched=launched, stats=stats)


def execute_sharded(sharded: ShardedIndex, queries: list, *,
                    batch_size: int = 32, depth: int = 2,
                    backend: str = "jax", max_results: int = 1 << 16,
                    max_group_size: int = batch_lib.MAX_GROUP_SIZE,
                    fuse: bool = True,
                    plan: "batch_lib.FusionPlan | None" = None,
                    stats: dict | None = None
                    ) -> list[QueryResult]:
    """Answer ``queries`` against the sharded index, pipelined at ``depth``
    (DESIGN.md §2.9): every batch fans out to all shards in one dispatch
    and results concatenate in part order — byte-identical to
    ``engine.query`` / ``batch.execute_batch`` on the unsharded index.
    ``fuse``/``plan`` coarsen each batch into megagroup families before
    the fan-out (DESIGN.md §2.10), so the per-batch dispatch count is
    O(#families) regardless of shard count."""
    pool_map = sharded.pool_map
    if fuse and plan is None:
        plan = batch_lib.FusionPlan()

    def schedule_fn(chunk, stats):
        groups = batch_lib.schedule(sharded.index, chunk, pool=pool_map,
                                    stats=stats)
        if fuse:
            groups = batch_lib.fuse_groups(groups, plan=plan, stats=stats)
        return groups

    def launch_fn(groups, n_queries, stats):
        return launch_groups_sharded(
            sharded, groups, n_queries=n_queries, backend=backend,
            max_results=max_results, max_group_size=max_group_size,
            stats=stats)

    return pipe_lib.execute_pipelined(
        sharded.index, queries, batch_size=batch_size, depth=depth,
        max_results=max_results, stats=stats,
        schedule_fn=schedule_fn, launch_fn=launch_fn)
