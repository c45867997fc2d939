"""HYB+M2 inverted index builder (paper §6.7, after Culpepper & Moffat [6]).

Lists with average gap ≤ B (i.e. len ≥ n_docs/B) become bitmaps; the rest are
compressed with the configured codec.  The corpus is split into ``n_parts``
doc-id ranges — the paper's L3-cache partitioning, which at cluster scale maps
1:1 onto data-parallel shards (DESIGN.md §2.5).

``codec_name="auto"`` turns on the build-time storage autotuner (DESIGN.md
§2.13): per posting list it computes closed-form byte estimates for every
codec family from the list's delta statistics (length, density, skew — no
trial encodes), combines them with a *measured* cost table (decode ns/int
per codec + gallop ns/probe, emitted by ``benchmarks/bench_decode.py
--json``; default table checked into ``configs/paper_index.py``), and picks
the family + skip policy minimizing estimated serve-plus-storage cost.
Every choice is lossless, so an autotuned index answers queries
byte-identically to a single-codec build — the differential tests assert
exactly that.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
from typing import Any

import numpy as np

from repro.core import bitmap as bm
from repro.core import codecs as codec_lib


@dataclasses.dataclass
class TermPosting:
    kind: str                  # 'list' | 'bitmap' | 'empty'
    payload: Any               # PackedList/PatchedList/VarintList/… | words
    n: int                     # postings in this part
    raw: np.ndarray | None = None   # kept for oracle checks in tests
    skip_ok: bool = True       # autotuner skip policy: False forces the
                               # decoded path even for skip-capable payloads


_part_uids = itertools.count()


@dataclasses.dataclass
class IndexPart:
    doc_lo: int
    doc_hi: int
    terms: dict[int, TermPosting]
    # process-unique id for cache keying: id(part) can be reused by the
    # allocator after a part is freed, which would let a long-lived
    # DecodeCache serve stale lists across index rebuilds
    uid: int = dataclasses.field(default_factory=lambda: next(_part_uids))


@dataclasses.dataclass
class HybridIndex:
    n_docs: int
    B: int                      # bitmap threshold (0 = no bitmaps)
    codec_name: str
    parts: list[IndexPart]

    def stats(self) -> dict:
        """Storage accounting by payload type via the codec registry
        (``codecs.codec_for``): bits/int and bytes/int over the whole
        index plus per-family list counts — the compression numbers
        serve.py and bench_engine report alongside q/s."""
        bits = 0.0
        n = 0
        counts: dict[str, int] = {}
        fam_bits: dict[str, float] = {}
        for part in self.parts:
            for tp in part.terms.values():
                n += tp.n
                if tp.kind == "bitmap":
                    fam, b = "bitmap", float(int(tp.payload.size) * 32)
                elif tp.kind == "list":
                    fam = codec_lib.family_of(tp.payload)
                    b = (codec_lib.codec_for(tp.payload)
                         .bits_per_int(tp.payload) * tp.n)
                else:
                    continue
                bits += b
                counts[fam] = counts.get(fam, 0) + 1
                fam_bits[fam] = fam_bits.get(fam, 0.0) + b
        return {"bits_per_int": bits / max(n, 1),
                "bytes_per_int": bits / 8 / max(n, 1),
                "postings": n,
                "codec_counts": counts,
                "codec_bytes": {k: int(v // 8) for k, v in fam_bits.items()}}


# --------------------------------------------------------------------------
# build-time storage autotuner (DESIGN.md §2.13)
# --------------------------------------------------------------------------

@dataclasses.dataclass
class CostModel:
    """Measured per-codec costs driving per-list codec + skip selection.

    ``decode_ns_per_int`` and ``dispatch_ns_per_list`` come straight from
    ``bench_decode.py --json`` (keys are codec names, e.g. ``bp-d1`` /
    ``varint``); the modeled decode wall-clock of one list is the fixed
    per-decode dispatch term plus ``n ·`` the per-int term
    (``_decode_cost``), and a family's score adds ``space_ns_per_byte ·
    bytes`` with bytes estimated closed-form from the list's delta
    statistics.  The dispatch term is what makes short lists interesting:
    on this container a device decode costs ~200–400 µs before the first
    int lands, so a host-decoded composite/varint list beats bitpack on
    *measured* wall clock below ~1 K ints even though its per-int cost is
    higher.  ``gallop_ns_per_probe`` prices the packed skip path: a long
    bitpacked list keeps ``skip_ok`` only when probing its skip index is
    estimated cheaper than decoding it outright.
    """
    decode_ns_per_int: dict[str, float]
    dispatch_ns_per_list: dict[str, float] = dataclasses.field(
        default_factory=dict)
    gallop_ns_per_probe: float = 90.0
    space_ns_per_byte: float = 2.0
    # a skip probe touches ~one candidate row per rare posting; this is
    # the reference candidate cardinality the skip-vs-decode comparison
    # assumes (the serve-time ratio test still gates per query).
    ref_probes: int = 4096

    def decode_ns(self, family: str) -> float:
        t = self.decode_ns_per_int
        return float(t.get(f"{family}-d1", t.get(family, 1.0)))

    def dispatch_ns(self, family: str) -> float:
        t = self.dispatch_ns_per_list
        return float(t.get(f"{family}-d1", t.get(family, 0.0)))

    @classmethod
    def resolve(cls, table=None) -> "CostModel":
        """table: None → checked-in default (configs.paper_index), str →
        path to a ``bench_decode --json`` dump, dict → inline table."""
        if table is None:
            from repro.configs.paper_index import DEFAULT_COST_TABLE
            table = DEFAULT_COST_TABLE
        elif isinstance(table, str):
            with open(table) as f:
                table = json.load(f)
        return cls(
            decode_ns_per_int=dict(table.get("decode_ns_per_int", {})),
            dispatch_ns_per_list=dict(table.get("dispatch_ns_per_list", {})),
            gallop_ns_per_probe=float(table.get("gallop_ns_per_probe", 90.0)),
            space_ns_per_byte=float(table.get("space_ns_per_byte", 2.0)))


def list_stats(seg: np.ndarray, span: int) -> dict:
    """Per-list statistics the autotuner scores on: length, density, and
    gap skew (max/mean delta ratio — high skew favors byte-granular and
    patched codecs over a per-block uniform bit width)."""
    n = int(seg.size)
    d = np.diff(seg.astype(np.int64), prepend=np.int64(0))
    mean_gap = float(d.mean()) if n else 0.0
    return {"n": n,
            "density": n / max(span, 1),
            "skew": float(d.max()) / max(mean_gap, 1e-9) if n else 0.0}


def _est_bytes(seg: np.ndarray) -> dict[str, float]:
    """Closed-form storage estimate per codec family from the D1 deltas —
    no trial encodes.  Mirrors each encoder's actual layout: bitpack pads
    to full blocks at the adaptive block size and pays the per-block max
    width; streamvbyte pays whole bytes + 2-bit control codes on 128-padded
    blocks; varint pays 7-bit groups; composite pays bitpack on the full-
    block prefix and varint on the tail."""
    n = int(seg.size)
    d = np.diff(seg.astype(np.int64), prepend=np.int64(0)).astype(np.uint64)
    bl = np.zeros(n, dtype=np.int64)
    nz = d > 0
    bl[nz] = np.floor(
        np.log2(d[nz].astype(np.float64))).astype(np.int64) + 1

    def block_bytes(rows: int, lens: np.ndarray) -> float:
        per = rows * 128
        k = max(-(-max(len(lens), 1) // per), 1)
        padded = np.zeros(k * per, np.int64)
        padded[: len(lens)] = lens
        widths = padded.reshape(k, per).max(axis=1)
        return float(widths.sum()) * per / 8 + k * 5     # +width/max meta

    rows = 8 if n <= 8192 else 32
    varint_b = float(np.maximum(-(-bl // 7), 1).sum())
    svb_pad = (-n) % 128
    svb_b = (float(np.maximum(-(-bl // 8), 1).sum()) + svb_pad
             + (n + svb_pad) / 4 + max(-(-n // 128), 1) * 8)
    bp_b = block_bytes(rows, bl)
    per8 = 8 * 128
    n_head = (n // per8) * per8
    comp_b = ((block_bytes(8, bl[:n_head]) if n_head else 0.0)
              + float(np.maximum(-(-bl[n_head:] // 7), 1).sum()))
    return {"bp": bp_b, "streamvbyte": svb_b, "varint": varint_b,
            "composite": comp_b}


# Below this many ints a bitpacked list can't reach SKIP_MIN_BLOCKS blocks
# at the adaptive block size, so packed serving is off the table and the
# decode-cost comparison decides alone.
_SKIP_MIN_INTS = 4 * 8 * 128


def _decode_cost(fam: str, n: int, cm: CostModel) -> float:
    """Modeled wall-clock ns to decode one n-int list: the family's fixed
    per-decode dispatch term plus a linear per-int term.  Composite is
    derived from its parts (bp8 head + varint tail) because its blend
    depends on n — the flat ``composite-d1`` table entry was measured at
    2^16 ints where the tail is negligible, which badly underestimates a
    short all-tail list."""
    if fam == "composite":
        per = 8 * 128
        n_head = (n // per) * per
        cost = cm.dispatch_ns("varint") + (n - n_head) * cm.decode_ns("varint")
        if n_head:
            cost += cm.dispatch_ns("bp8") + n_head * cm.decode_ns("bp8")
        return cost
    if fam == "bp" and n <= 8192:
        fam = "bp8"     # bitpack.encode adapts to 8-row blocks here
    return cm.dispatch_ns(fam) + n * cm.decode_ns(fam)


def autotune_choice(seg: np.ndarray, span: int, cm: CostModel,
                    mode: str = "d1") -> tuple[str, bool]:
    """Pick (codec name, skip_ok) for one posting list."""
    n = int(seg.size)
    if n >= _SKIP_MIN_INTS:
        # long lists: bitpack — the only skip-capable layout — and keep the
        # skip index only when probing beats decoding at reference load
        skip_ok = (cm.gallop_ns_per_probe * cm.ref_probes
                   < _decode_cost("bp", n, cm))
        return f"bp-{mode}", skip_ok
    est = _est_bytes(seg)
    score = {fam: _decode_cost(fam, n, cm) + cm.space_ns_per_byte * b
             for fam, b in est.items()}
    fam = min(score, key=score.get)
    name = "varint" if fam == "varint" else f"{fam}-{mode}"
    return name, fam == "bp"


def build(postings: list[np.ndarray], n_docs: int, codec_name: str = "bp-d1",
          B: int = 0, n_parts: int = 1, keep_raw: bool = False,
          varint_tail_below: int = 1024,
          precompute_layouts: bool = True,
          cost_table=None) -> HybridIndex:
    """varint_tail_below: lists shorter than this are stored Varint — the
    paper's tail-codec rule (block packing pays block/n × padding overhead on
    tiny lists; EXPERIMENTS §Perf c4).  ``codec_name="auto"`` replaces the
    fixed codec + tail rule with the cost-model autotuner (module docstring);
    ``cost_table`` feeds it a ``bench_decode --json`` table (path or dict,
    None = the checked-in default).

    precompute_layouts: project every skip-capable list onto its self-padded
    batch-uniform PackedLayout at build time (memoized per payload uid in
    the posting-source layer), so serving never pays the projection on the
    query path (DESIGN.md §2.8)."""
    auto = codec_name == "auto"
    cm = CostModel.resolve(cost_table) if auto else None
    codec = codec_lib.get_codec(codec_name)
    tail_codec = codec_lib.get_codec("varint")
    bounds = np.linspace(0, n_docs, n_parts + 1).astype(np.int64)
    parts = []
    for p in range(n_parts):
        lo, hi = int(bounds[p]), int(bounds[p + 1])
        span = max(hi - lo, 1)
        terms: dict[int, TermPosting] = {}
        for tid, docs in enumerate(postings):
            seg = docs[(docs >= lo) & (docs < hi)] - lo
            if seg.size == 0:
                terms[tid] = TermPosting("empty", None, 0)
                continue
            avg_gap = span / seg.size
            if B > 0 and avg_gap <= B:
                terms[tid] = TermPosting(
                    "bitmap", bm.build_np(seg, span), int(seg.size),
                    raw=seg if keep_raw else None)
            elif auto:
                name, skip_ok = autotune_choice(seg, span, cm)
                terms[tid] = TermPosting(
                    "list", codec_lib.get_codec(name).encode(seg),
                    int(seg.size), raw=seg if keep_raw else None,
                    skip_ok=skip_ok)
            else:
                c = tail_codec if (codec_name != "varint"
                                   and seg.size < varint_tail_below) else codec
                terms[tid] = TermPosting(
                    "list", c.encode(seg), int(seg.size),
                    raw=seg if keep_raw else None)
        parts.append(IndexPart(lo, hi, terms))
    if precompute_layouts:
        from repro.index import source
        source.precompute_layouts(parts)
    return HybridIndex(n_docs=n_docs, B=B, codec_name=codec_name, parts=parts)


def warmup_serving(index: HybridIndex, queries: list[list[int]] | None = None,
                   *, plan=None, batch_size: int = 32, backend: str = "jax",
                   pool=None, **kwargs) -> dict:
    """Build-time AOT signature warmup (DESIGN.md §2.10): precompile the
    fused family ladder so the first served batch never stalls on jit
    compiles.  ``queries`` should be a representative workload sample when
    one exists (e.g. a replayed log slice); None synthesizes one from the
    index term stats.  Returns the ``batch.warmup`` report and the plan it
    warmed (pass both to the serving loop)."""
    from repro.index import batch as batch_lib
    if plan is None:
        plan = batch_lib.FusionPlan()
    report = batch_lib.warmup(index, queries, plan=plan,
                              batch_size=batch_size, backend=backend,
                              pool=pool, **kwargs)
    report["plan"] = plan
    return report


def build_sharded(postings: list[np.ndarray], n_docs: int, *, n_shards: int,
                  codec_name: str = "bp-d1", B: int = 0,
                  n_parts: int | None = None, keep_raw: bool = False,
                  varint_tail_below: int = 1024,
                  capacity_ints: int | None = None, warm: bool = True,
                  cost_table=None):
    """Per-part build placed onto data-parallel shards (DESIGN.md §2.5).

    Builds ``n_parts`` doc-id-range parts (default ``n_shards`` — the 1:1
    part↔shard mapping the paper's partitioning suggests at cluster scale)
    and returns a ``repro.index.shard.ShardedIndex`` carrying the
    part→shard→device placement map, with each shard's working set staged
    on its own device when ``warm``."""
    if n_parts is None:
        n_parts = n_shards
    idx = build(postings, n_docs, codec_name=codec_name, B=B,
                n_parts=n_parts, keep_raw=keep_raw,
                varint_tail_below=varint_tail_below, cost_table=cost_table)
    from repro.index import shard as shard_lib
    return shard_lib.shard_index(idx, n_shards, capacity_ints=capacity_ints,
                                 warm=warm)
