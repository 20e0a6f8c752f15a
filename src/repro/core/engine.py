"""Vectorized CEMR engine: stage/kernel construction for tile enumeration.

TPU-native adaptation of the paper's DFS enumeration (DESIGN.md §2):

  * a *tile* is a fixed-capacity batch of (aggregated) partial embeddings:
    IDX columns (deterministically mapped vertices, one int32 per row) and
    BM columns (aggregated white mappings, uint32 bitmaps over per-label
    candidate spaces);
  * extending u_i = gather adjacency bitmap rows for the backward-neighbor
    mappings and AND them (the `bitmap_intersect` hot loop — Pallas kernel,
    compiled on TPU / interpret on CPU, or the jnp gather oracle);
  * CEM: Case-2/4.2 extensions *store* R as a bitmap column — whole sub-trees
    advance as one row (the paper's aggregated embeddings);
  * expansion to IDX columns is a fixed-capacity enumeration of set bits
    (`bitops.expand_select`); overflow re-enters the host work stack, giving
    DFS-over-tiles bounded memory and anytime results;
  * CER: rows whose extension read-set (BK columns + same-label IDX columns)
    coincide are brother embeddings — one extension computation serves the
    whole class, either through the cross-tile CER ring buffer (scheduler.py)
    or the per-tile bucketed compute below;
  * contained-vertex pruning = per-row popcount threshold;
  * injectivity: IDX values of the same label are pairwise distinct by eager
    bit-clearing; BM columns are kept disjoint from same-label IDX values;
    same-label BM×BM overlap is corrected exactly at the leaf by
    inclusion-exclusion (groups capped at 3 by the encoder).

This module owns the *static* side: the stage plan, the per-stage compute /
expand / dedup closures, and their jitted wrappers. The *runtime* side — the
device-resident superstep loop, frontier compaction, the CER buffer, and
on-device leaf counting — lives in scheduler.py; `VectorEngine.run()`
delegates to it.
"""
from __future__ import annotations

import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from . import bitops
from .count import iter_injective
from .encoding import QueryAnalysis
from .filtering import CandidateSpace
from .graph import Graph
from .plan import (BM, IDX, INTERSECT_MODES, LevelOp, MatchingPlan,
                   build_plan)
from .ref_engine import preprocess

__all__ = ["VectorMatchResult", "VectorStats", "vector_match", "VectorEngine",
           "TablePack", "INTERSECT_MODES"]


@dataclasses.dataclass
class VectorStats:
    """Counters for one vector-engine run. See docs/engine.md for the field
    glossary; `device_steps` counts jitted host→device dispatches (one per
    superstep / merge / legacy kernel call), never double-charged."""

    device_steps: int = 0
    supersteps: int = 0
    tiles: int = 0
    expansions: int = 0
    rows_processed: int = 0
    rows_alive: int = 0
    gather_and_ops: int = 0          # adjacency rows gathered+ANDed (work proxy)
    dedup_keys_seen: int = 0
    dedup_unique: int = 0
    cer_hits: int = 0                # rows served from the cross-tile CER buffer
    cer_misses: int = 0
    fail_hits: int = 0               # frontier rows masked dead by the failure
                                     # cache (one per matching stage lookup)
    fail_misses: int = 0             # failure-cache lookups finding no entry
    fail_inserts: int = 0            # failed read-sets recorded in the ring
    fail_pruned_rows: int = 0        # rows killed before their subtree was
                                     # dispatched (<= fail_hits: a row hit by
                                     # several stage lookups prunes once)
    bucketed_tiles: int = 0          # per-tile CER bucketed computes (compat path)
    packed_tiles: int = 0            # sibling-tile merges (frontier compaction)
    batched_queries: int = 0         # queries advanced by this superbatch run
    bucket_recompiles: int = 0       # batched supersteps jitted fresh this run
    shard_lanes: int = 0             # live lanes dispatched by sharded supersteps
    shard_rebalances: int = 0        # idle lanes refilled by chunk splits /
                                     # pending flushes (host-side rebalance)
    leaf_tiles: int = 0
    leaf_overflows: int = 0          # uint64 leaf reductions that fell back to host
    peak_stack: int = 0
    readbacks: int = 0               # host sync points (device_get calls) on the
                                     # fused/sharded superstep paths; overlap
                                     # coalesces them: readbacks <= supersteps
    overlapped_supersteps: int = 0   # supersteps dispatched while an earlier
                                     # dispatch's readback was still outstanding
    pad_copy_bytes: int = 0          # bytes of the zero-padded table copies the
                                     # Pallas intersect kernel makes, charged per
                                     # dispatch like gather_and_ops
    dispatch_buffers: int = 0        # flattened input plus output leaves of
                                     # every superstep call (device buffers the
                                     # runtime takes in and hands back)
    # wall times of the host spans (core/spans.py), in seconds: timings, not
    # counters, so equality of two stats compares the counters only
    span_count_s: float = dataclasses.field(default=0.0, compare=False)
    span_plan_s: float = dataclasses.field(default=0.0, compare=False)
    span_enumerate_s: float = dataclasses.field(default=0.0, compare=False)
    span_dispatch_s: float = dataclasses.field(default=0.0, compare=False)
    span_readback_s: float = dataclasses.field(default=0.0, compare=False)

    @property
    def dedup_ratio(self) -> float:
        return (self.dedup_unique / self.dedup_keys_seen
                if self.dedup_keys_seen else 1.0)


@dataclasses.dataclass
class VectorMatchResult:
    count: int
    stats: VectorStats
    timed_out: bool
    embeddings: list[dict[int, int]] | None = None


class TablePack:
    """A plan's adjacency tables and candidate masks, packed at engine build
    into one 2-D uint32 device buffer per word width W (`groups`, widths
    ascending): the rows of every table of width W, then every mask of width
    W as one more row each. `table_at["u:w"] = (group, row offset, rows)`
    and `mask_at[u] = (group, row)` are fixed here, so a jitted step takes
    a handful of buffers and slices its tables out statically (`view`)."""

    def __init__(self, tables: dict, masks: dict):
        stacks: dict[int, list] = {}              # width -> stacked arrays

        def put(arr):                             # -> (width, row offset)
            parts = stacks.setdefault(arr.shape[1], [])
            parts.append(arr)
            return arr.shape[1], sum(a.shape[0] for a in parts[:-1])

        tab = {f"{u}:{w}": (*put(t), t.shape[0])
               for (u, w), t in tables.items()}
        msk = {u: put(m[None, :]) for u, m in masks.items()}
        self.widths = sorted(stacks)
        g_of = {w: g for g, w in enumerate(self.widths)}
        self.table_at = {k: (g_of[w], o, n) for k, (w, o, n) in tab.items()}
        self.mask_at = {u: (g_of[w], o) for u, (w, o) in msk.items()}
        self.groups = tuple(
            jnp.asarray(np.concatenate(stacks[w]).astype(np.uint32))
            for w in self.widths)

    def shape(self, key: str) -> tuple[int, int]:
        """(rows, words) of table `key`."""
        g, _, n = self.table_at[key]
        return n, self.widths[g]

    def view(self, groups):
        """Trace-time (tables, masks) dicts over `groups` (this pack's
        buffers, as the jitted step receives them): static row slices under
        the plan's keys, which the stage closures read as before."""
        tables = {k: groups[g][o:o + n]
                  for k, (g, o, n) in self.table_at.items()}
        masks = {u: groups[g][o] for u, (g, o) in self.mask_at.items()}
        return tables, masks


# ---------------------------------------------------------------------------
# jitted step functions (built per level, cached per engine instance)
# ---------------------------------------------------------------------------

def _union_rows(table, bmcol):
    """OR of adjacency rows selected by a bitmap column (no-black-bwd path).
    Formulated as a boolean matmul: MXU-friendly on TPU."""
    s = table.shape[0]
    t = bmcol.shape[0]
    # unpack source bits -> (T, S)
    word = jnp.arange(s, dtype=jnp.int32) >> 5
    bit = (jnp.arange(s, dtype=jnp.int32) & 31).astype(jnp.uint32)
    src_bits = ((bmcol[:, word] >> bit[None, :]) & jnp.uint32(1)).astype(jnp.int32)
    # unpack table bits -> (S, 32*W); matmul; repack
    w = table.shape[1]
    tab_bits = ((table[:, :, None] >> jnp.arange(32, dtype=jnp.uint32)[None, None, :])
                & jnp.uint32(1)).astype(jnp.int32).reshape(s, w * 32)
    hit = (src_bits @ tab_bits) > 0                       # (T, 32W)
    hit = hit.reshape(t, w, 32)
    packed = (hit.astype(jnp.uint32)
              << jnp.arange(32, dtype=jnp.uint32)[None, None, :]).sum(axis=2,
                                                                      dtype=jnp.uint32)
    return packed


def _resolve_intersect_fn(intersect: str):
    """Map the `intersect` knob to an intersect_fn (or None = jnp gather):
    "auto" = Pallas compiled on TPU, jnp oracle elsewhere (interpret-mode
    Pallas is a correctness tool, not a perf path); "pallas" = force the
    kernel (interpret on non-TPU); "jnp" = force the oracle."""
    if intersect not in INTERSECT_MODES:
        raise ValueError(f"intersect must be one of {INTERSECT_MODES}, "
                         f"got {intersect!r}")
    from repro.kernels import ops as _kops
    if intersect == "pallas" or (intersect == "auto" and _kops.on_tpu()):
        return _kops.make_intersect_fn(use_pallas=True)
    # "fused" routes the boundary expand+intersect through the fused Pallas
    # kernel (engine._make_expand_fused); the remaining computes stay jnp
    return None


class VectorEngine:
    """Compiled matcher for one (query, data, encoding) plan."""

    def __init__(self, cs: CandidateSpace, an: QueryAnalysis, *,
                 tile_rows: int = 256, use_cv: bool = True,
                 use_dedup: bool = True, intersect_fn=None,
                 plan: MatchingPlan | None = None, intersect: str = "auto",
                 use_cer_buffer: bool = True, cer_buffer_slots: int = 256,
                 use_failure_cache: bool = True,
                 failure_cache_slots: int = 64,
                 pack_tiles: bool = True, mesh=None, overlap: bool = True):
        # `plan` lets a session layer (repro.api.Matcher) build the plan once
        # and share it across engine configurations. `mesh` is a jax Mesh
        # with a "data" axis (launch.mesh.make_enum_mesh); size > 1 selects
        # the sharded scheduler (core.shard), None/size-1 the single-device
        # path; each shard lane runs full-width tiles, so one sharded
        # dispatch covers up to n_shards frontier chunks at once.
        self.plan = build_plan(cs, an) if plan is None else plan
        self.cs, self.an = cs, an
        self.t = tile_rows
        self.use_cv = use_cv
        self.use_dedup = use_dedup
        self.use_cer_buffer = use_cer_buffer
        self.cer_buffer_slots = cer_buffer_slots
        self.use_failure_cache = use_failure_cache
        self.failure_cache_slots = failure_cache_slots
        self.pack_tiles = pack_tiles
        self.mesh = mesh
        # overlap only changes *when* superstep readbacks happen (deferred /
        # coalesced device_get), never what is computed — the schedulers
        # share one claim-and-dispatch discipline for both settings
        self.overlap = overlap
        self.fused_expand = intersect == "fused" and intersect_fn is None
        if intersect_fn is None:
            intersect_fn = _resolve_intersect_fn(intersect)
        self.intersect_fn = intersect_fn  # pluggable kernel (Pallas ops)
        p = self.plan
        # the only device copy of the tables and masks (TablePack.view)
        self.packed = TablePack(p.tables, p.masks)
        self.stats = VectorStats()
        self._stages = self._build_stages()
        self._jit_cache: dict = {}
        self._scheduler = None

    # ------------------------------------------------------------- stage plan
    def _build_stages(self):
        """Flatten per-level ops into micro-op stages. Stage kinds:
        ('decompose', vertex, slot, same_bm, words_src)
        ('extend', LevelOp)
        Stage s consumes a tile and either emits a tile for stage s+1 or a
        pending expansion."""
        stages: list = []
        # root pseudo-op
        root_op = LevelOp(vertex=self.plan.root_vertex, case=1, store=IDX,
                          bk_pairs=[], wt_vertices=[], union_src=-1,
                          decompose=[], con_threshold=len(self.an.con[0]),
                          same_label_idx_slots=[], same_label_bm=[],
                          dedup_slots=[], n_words=self.plan.root_words,
                          idx_slot=0, level=0)
        stages.append(("extend", root_op))
        for op in self.plan.ops:
            for (v, slot, same_bm) in op.decompose:
                words_src = self.plan.words[self.plan.label_of[v]]
                stages.append(("decompose", v, slot, same_bm, words_src))
            stages.append(("extend", op))
        return stages

    # ----------------------------------------------------------- raw closures
    # The scheduler composes these untraced closures into fused supersteps;
    # the jitted wrappers below serve the per-stage compat path.

    def _make_compute_parts(self, si: int):
        """Return (compute_r, con): compute_r(tile, tables, masks) -> (r, pop)
        produces the extension bitmap *before* any aliveness interaction —
        pure in the extension read-set, which is what makes the result
        cacheable in the CER buffer."""
        stage = self._stages[si]

        if stage[0] == "decompose":
            _, v, slot, same_bm, words_src = stage

            def compute_r(tile, tables, masks):
                r = tile["bm"][v]
                return r, bitops.row_popcount(r)

            return compute_r, 1

        op: LevelOp = stage[1]
        pairs = [(s, u, op.vertex) for (s, u) in op.bk_pairs]
        con = max(op.con_threshold, 1) if self.use_cv else 1
        root = op.level == 0
        ext_fn = self.intersect_fn

        def compute_r(tile, tables, masks):
            pop = None
            if root:
                r = jnp.broadcast_to(masks[op.vertex][None, :],
                                     (tile["alive"].shape[0], op.n_words))
            elif pairs:
                if ext_fn is not None:
                    tabs = [tables[f"{u}:{w}"] for (_, u, w) in pairs]
                    idxs = jnp.stack([tile["idx"][:, s] for (s, _, _) in pairs], 1)
                    out = ext_fn(tabs, idxs)
                    if not (isinstance(out, tuple) and len(out) == 2):
                        raise TypeError(
                            "intersect_fn must return (R, pop) — the ANDed "
                            "bitmap and its fused per-row popcount (see "
                            "kernels.ops.make_intersect_fn). Returning R "
                            "alone was the pre-scheduler contract.")
                    r, pop = out                  # fused popcount from kernel
                else:
                    r = None
                    for (s, u_j, u_i) in pairs:
                        rows = tables[f"{u_j}:{u_i}"][tile["idx"][:, s]]
                        r = rows if r is None else (r & rows)
            else:
                r = _union_rows(tables[f"{op.union_src}:{op.vertex}"],
                                tile["bm"][op.union_src])
            cleared = jnp.int32(0)
            for s in op.same_label_idx_slots:
                r, c = bitops.clear_bit_rows_count(r, tile["idx"][:, s])
                cleared = cleared + c
            pop = bitops.row_popcount(r) if pop is None else pop - cleared
            return r, pop

        return compute_r, con

    @staticmethod
    def finish_compute(tile, r, pop, con):
        """Aliveness + contained-vertex prune; dead rows' bitmaps are zeroed
        so downstream bit enumeration and merges see only live work."""
        ok = tile["alive"] & (pop >= con) & (pop > 0)
        r = jnp.where(ok[:, None], r, jnp.uint32(0))
        pop = jnp.where(ok, pop, 0)
        return r, pop, ok

    def _make_expand(self, si: int, *, with_sel: bool = False):
        stage = self._stages[si]
        t_out = self.t
        if stage[0] == "decompose":
            _, v, slot, same_bm, _ = stage
            wt_prune: list[tuple[int, str]] = []
            same_label_bm = list(same_bm)
            drop_bm = v
        else:
            op: LevelOp = stage[1]
            wt_prune = [(u_j, f"{op.vertex}:{u_j}") for u_j in op.wt_vertices]
            same_label_bm = list(op.same_label_bm)
            drop_bm = None

        def expand(tile, r, start, tables):
            rows, bitpos, valid, total = bitops.expand_select(r, start, t_out)
            idx = tile["idx"][rows]
            idx = jnp.concatenate([idx, bitpos[:, None]], axis=1)
            bm_out = {}
            alive = valid
            for u, col in tile["bm"].items():
                if u == drop_bm:
                    continue
                g = col[rows]
                for (u_j, tkey) in wt_prune:
                    if u_j == u:
                        g = g & tables[tkey][bitpos]
                if u in same_label_bm:
                    g = bitops.clear_bit_rows(g, bitpos)
                alive = alive & (bitops.row_popcount(g) > 0)
                bm_out[u] = g
            out = {"idx": idx, "bm": bm_out, "alive": alive}
            if with_sel:
                # expose the raw bit selection so the fused Pallas kernel
                # can double-indirect through (rows, bitpos) itself
                return out, total, rows, bitpos
            return out, total

        return expand

    def _make_expand_fused(self, si: int, sj: int):
        """Fused expand+intersect+popcount across the boundary between
        expand stage `si` and the extend stage `sj` that follows it: one
        Pallas kernel consumes the bit selection straight from
        `bitops.expand_select` and produces the child intersection
        (R, pop) without materializing the per-pair gathered rows.

        Returns None when the fused path is off (`intersect != "fused"`)
        or the stage pair is ineligible (root / union / decompose
        extends have no backward-pair intersection to fuse). The kernel
        never masks dead rows — (R, pop) must stay a pure function of
        the key columns so CER cache entries remain sound;
        `finish_compute` masks downstream, exactly like the jnp path."""
        if not self.fused_expand:
            return None
        stage = self._stages[sj]
        if stage[0] != "extend":
            return None
        op: LevelOp = stage[1]
        if op.level == 0 or not op.bk_pairs:
            return None
        from repro.kernels import ops as _kops
        pairs = [(s, u, op.vertex) for (s, u) in op.bk_pairs]
        slots = tuple(s for (s, _, _) in pairs)
        fused_fn = _kops.make_fused_expand_intersect_fn()
        expand = self._make_expand(si, with_sel=True)
        same_slots = list(op.same_label_idx_slots)

        def fused(tile, r, start, tables):
            out, total, rows, bitpos = expand(tile, r, start, tables)
            tabs = [tables[f"{u}:{w}"] for (_, u, w) in pairs]
            r2, pop = fused_fn(tabs, tile["idx"], rows, bitpos, slots)
            cleared = jnp.int32(0)
            for s in same_slots:
                r2, c = bitops.clear_bit_rows_count(r2, out["idx"][:, s])
                cleared = cleared + c
            return out, total, (r2, pop - cleared)

        return fused

    def _make_leaf_terms(self):
        """tile -> (T, n_terms) int32 popcount terms for leaf counting
        (singles, then per-group inclusion-exclusion terms)."""
        plan = self.plan
        singles = list(plan.leaf_singles)
        groups = [list(g) for g in plan.leaf_groups]

        def leaf(tile):
            terms = []
            for u in singles:
                terms.append(bitops.row_popcount(tile["bm"][u]))
            for g in groups:
                if len(g) == 2:
                    a, b = tile["bm"][g[0]], tile["bm"][g[1]]
                    terms += [bitops.row_popcount(a), bitops.row_popcount(b),
                              bitops.row_popcount(a & b)]
                else:  # len 3 (encoder cap)
                    a, b, c = (tile["bm"][g[0]], tile["bm"][g[1]],
                               tile["bm"][g[2]])
                    terms += [bitops.row_popcount(a), bitops.row_popcount(b),
                              bitops.row_popcount(c),
                              bitops.row_popcount(a & b),
                              bitops.row_popcount(a & c),
                              bitops.row_popcount(b & c),
                              bitops.row_popcount(a & b & c)]
            return (jnp.stack(terms, axis=1) if terms
                    else jnp.zeros((tile["alive"].shape[0], 0), jnp.int32))

        return leaf

    # -------------------------------------------------------------- jit steps
    def _compute_fn(self, si: int):
        key = ("compute", si)
        if key in self._jit_cache:
            return self._jit_cache[key]
        compute_r, con = self._make_compute_parts(si)

        def compute(tile, groups):
            r, pop = compute_r(tile, *self.packed.view(groups))
            r, pop, ok = self.finish_compute(tile, r, pop, con)
            return r, ok

        fn = jax.jit(compute)
        self._jit_cache[key] = fn
        return fn

    def _expand_fn(self, si: int):
        key = ("expand", si)
        if key in self._jit_cache:
            return self._jit_cache[key]
        expand = self._make_expand(si)

        def expand_packed(tile, r, start, groups):
            return expand(tile, r, start, self.packed.view(groups)[0])

        fn = jax.jit(expand_packed)
        self._jit_cache[key] = fn
        return fn

    def _leaf_fn(self):
        key = ("leaf",)
        if key in self._jit_cache:
            return self._jit_cache[key]
        leaf_terms = self._make_leaf_terms()

        def leaf(tile):
            return leaf_terms(tile), tile["alive"]

        fn = jax.jit(leaf)
        self._jit_cache[key] = fn
        return fn

    def _dedup_fn(self, si: int):
        """Brother-embedding analysis (vectorized CER): group rows by the
        extension read-set columns. Returns (n_unique, rep_rows, group_of):
        rep_rows[g] = row index of group g's representative; group_of[t] =
        group id of row t (undefined for dead rows)."""
        key = ("dedup", si)
        if key in self._jit_cache:
            return self._jit_cache[key]
        op: LevelOp = self._stages[si][1]
        slots = list(op.dedup_slots)

        def uniq(tile):
            t = tile["alive"].shape[0]
            cols = [tile["idx"][:, s] for s in slots]
            order = jnp.lexsort(tuple(cols[::-1]) + (~tile["alive"],))
            sorted_cols = [c[order] for c in cols]
            alive_s = tile["alive"][order]
            diff = jnp.zeros(t, bool).at[0].set(True)
            for c in sorted_cols:
                diff = diff | jnp.concatenate([jnp.ones(1, bool),
                                               c[1:] != c[:-1]])
            gid_sorted = jnp.cumsum(diff.astype(jnp.int32)) - 1
            n_unique = jnp.sum(diff & alive_s)
            rep_rows = jnp.zeros(t, jnp.int32).at[gid_sorted].max(
                jnp.where(diff, order, 0).astype(jnp.int32))
            group_of = jnp.zeros(t, jnp.int32).at[order].set(gid_sorted)
            return n_unique, rep_rows, group_of

        fn = jax.jit(uniq)
        self._jit_cache[key] = fn
        return fn

    def _bucket_compute_fn(self, si: int, bucket: int):
        """CER-bucketed extension: run the gather+AND on `bucket` unique
        representative rows instead of the full tile, then broadcast R back
        through group ids — the vectorized realization of the paper's CEB
        reuse (one extension computation per brother-embedding class)."""
        key = ("bucket", si, bucket)
        if key in self._jit_cache:
            return self._jit_cache[key]
        op: LevelOp = self._stages[si][1]
        pairs = [(s, u, op.vertex) for (s, u) in op.bk_pairs]
        con = max(op.con_threshold, 1) if self.use_cv else 1

        def compute(tile, rep_rows, group_of, groups):
            tables, _ = self.packed.view(groups)
            reps = rep_rows[:bucket]
            idx_b = tile["idx"][reps]
            alive_b = tile["alive"][reps]
            r = None
            for (s, u_j, u_i) in pairs:
                rows = tables[f"{u_j}:{u_i}"][idx_b[:, s]]
                r = rows if r is None else (r & rows)
            r = jnp.where(alive_b[:, None], r, jnp.uint32(0))
            # broadcast per-group results back to all rows
            r_full = r[jnp.clip(group_of, 0, bucket - 1)]
            for s in op.same_label_idx_slots:
                r_full = bitops.clear_bit_rows(r_full, tile["idx"][:, s])
            pop = bitops.row_popcount(r_full)
            ok = tile["alive"] & (pop >= con) & (pop > 0)
            r_full = jnp.where(ok[:, None], r_full, jnp.uint32(0))
            return r_full, ok

        fn = jax.jit(compute)
        self._jit_cache[key] = fn
        return fn

    # --------------------------------------------------------------- schedule
    def run(self, *, limit: int = 1_000_000, max_steps: int | None = None,
            materialize: bool = False) -> VectorMatchResult:
        if self._scheduler is None:
            if self.mesh is not None and self.mesh.devices.size > 1:
                from .shard import ShardedTileScheduler
                self._scheduler = ShardedTileScheduler(self, self.mesh)
            else:
                from .scheduler import TileScheduler
                self._scheduler = TileScheduler(self)
        return self._scheduler.run(limit=limit, max_steps=max_steps,
                                   materialize=materialize)

    # ------------------------------------------------------------ materialize
    def _materialize(self, tile, cap: int) -> list[dict[int, int]]:
        """Decode at most `cap` explicit embeddings from a leaf tile. One
        row's bitmap sets expand to their injective product, which on a
        large graph outgrows any limit (and the host's memory), so the
        decoding stops at `cap`."""
        return list(itertools.islice(self._iter_embeddings(tile),
                                     max(cap, 0)))

    def _iter_embeddings(self, tile):
        plan = self.plan
        idx = np.asarray(tile["idx"])
        alive = np.asarray(tile["alive"])
        # a host copy of a TPU array can keep a strided layout, and a row
        # must be contiguous to be viewed as bytes
        bm = {u: np.ascontiguousarray(v) for u, v in tile["bm"].items()}
        for row in np.nonzero(alive)[0]:
            base = {}
            for k, u in enumerate(plan.idx_slots):
                space = plan.spaces[plan.label_of[u]]
                base[u] = int(space[idx[row, k]])
            # decode bitmap sets
            sets: dict[int, np.ndarray] = {}
            for u, col in bm.items():
                bits = np.nonzero(np.unpackbits(
                    col[row].view(np.uint8), bitorder="little"))[0]
                space = plan.spaces[plan.label_of[u]]
                sets[u] = space[bits[bits < space.shape[0]]]
            groups: dict[int, list[int]] = {}
            for u in sets:
                groups.setdefault(plan.label_of[u], []).append(u)
            group_list = list(groups.values())

            def rec(gi, acc):
                if gi == len(group_list):
                    yield dict(acc)
                    return
                us = group_list[gi]
                for combo in iter_injective([sets[u] for u in us]):
                    acc2 = dict(acc)
                    for u, v in zip(us, combo):
                        acc2[u] = int(v)
                    yield from rec(gi + 1, acc2)

            yield from rec(0, base)


def vector_match(query: Graph, data: Graph, *, encoding: str = "cost",
                 tile_rows: int = 256, limit: int = 1_000_000,
                 max_steps: int | None = None, materialize: bool = False,
                 use_cv: bool = True, use_dedup: bool = True,
                 intersect_fn=None, order: list[int] | None = None,
                 intersect: str = "auto", use_cer_buffer: bool = True,
                 cer_buffer_slots: int = 256, use_failure_cache: bool = True,
                 failure_cache_slots: int = 64, pack_tiles: bool = True,
                 mesh=None, overlap: bool = True) -> VectorMatchResult:
    """End-to-end vectorized CEMR matching (preprocess + tile enumeration)."""
    cs, an = preprocess(query, data, encoding=encoding, order=order)
    if any(c.shape[0] == 0 for c in cs.cand):
        return VectorMatchResult(count=0, stats=VectorStats(), timed_out=False,
                                 embeddings=[] if materialize else None)
    eng = VectorEngine(cs, an, tile_rows=tile_rows, use_cv=use_cv,
                       use_dedup=use_dedup, intersect_fn=intersect_fn,
                       intersect=intersect, use_cer_buffer=use_cer_buffer,
                       cer_buffer_slots=cer_buffer_slots,
                       use_failure_cache=use_failure_cache,
                       failure_cache_slots=failure_cache_slots,
                       pack_tiles=pack_tiles, mesh=mesh, overlap=overlap)
    return eng.run(limit=limit, max_steps=max_steps, materialize=materialize)
