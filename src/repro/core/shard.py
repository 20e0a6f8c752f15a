"""Multi-device sharded enumeration: data-parallel tile scheduling.

CEMR's search tree is embarrassingly parallel at the root: each root
candidate's subtree can be enumerated independently and the per-query
counts summed, while the CER buffers and pruning stay local to each worker
(the failure-reuse locality argument of Arai et al.). This module runs the
fused ladder supersteps of `core.scheduler` *data-parallel across a device
mesh*:

  * **Root partition** — the level-0 candidate bitmap is split into
    disjoint per-shard partitions by a degree-weighted balance heuristic
    (`plan.root_extension_weights` scores each candidate by its level-1
    fanout, `distributed.sharding.partition_bitmap` assigns
    heaviest-first). Each partition enters the work pool as its own root
    item carrying its partition mask; the superstep ANDs that mask into
    the *already pruned* root extension (contained-vertex thresholds are
    always judged on the global popcount, never a partition's), so a
    shard only ever enumerates its own subtrees.

  * **shard_map supersteps** — one dispatch advances `n_shards` lanes in
    lockstep through the same jitted ladder (`jax.shard_map` over a 1-D
    "data" mesh): bitmap-adjacency tables and candidate masks are
    replicated (committed to every device once at construction), tiles /
    frontiers / cursors / partition masks are split along the lane axis,
    and every lane keeps its *own* CER ring buffers. On-device leaf
    counts are `psum`-reduced across the mesh so the host reads one
    replicated total per superstep; the int64-overflow → exact host
    big-int fallback stays per shard (only an overflowing lane's terms
    are recounted on the host).

  * **Host-side rebalance** — work items live in one *global* pool, not in
    per-shard queues, so a shard whose frontier drains immediately picks
    up any other shard's items at the same boundary (work stealing by
    construction). Idle lanes are additionally refilled by (a) flushing a
    parked sub-capacity pending frontier at the dispatch boundary and (b)
    *chunk-splitting*: an overflowing frontier's remaining expansion
    chunks (disjoint `cursor` windows over the same (tile, R)) fan out
    across idle lanes — this is what keeps a deliberately skewed workload
    (one hot root candidate) from serializing on one shard. Repartitioned
    sub-capacity frontiers continue to merge through the existing
    compaction machinery (`pack_tiles`), which is lane-agnostic.
    `VectorStats.shard_rebalances` counts the refills.

With one device the mesh resolves to None upstream and the plain
single-device schedulers run — the fallback is bit-identical by
construction. `ShardedSuperbatchScheduler` composes the cross-query
superbatch (query-id lanes) with the shard axis: each query's root
candidates are partitioned per shard, and the per-query leaf segment-sums
are psum-reduced across the mesh.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.sharding import partition_bitmap

from .engine import VectorMatchResult, VectorStats
from .plan import root_extension_weights
from .scheduler import (SuperbatchScheduler, TileScheduler, _charge_buffers,
                        _sync_inflight, leaf_count_host)

__all__ = ["ShardedTileScheduler", "ShardedSuperbatchScheduler"]

_SH = P("data")


def _lane_slice(tree, s: int):
    """Lane `s`'s slice of a lane-stacked pytree (lazy device gathers)."""
    return jax.tree.map(lambda x: x[s], tree)


def _lane_stack(trees):
    """Stack per-lane pytrees along a new leading lane axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


class _ShardLoopBase:
    """Machinery shared by the single-query and superbatch sharded
    schedulers: the global work pool, lane filling (with rebalance),
    frontier routing, the shard_map superstep wrapper, and the per-lane
    ladder walk. Work items are
    (boundary, tile, r, cursor, total_bits, part_mask) — `total_bits` is
    always known at push time, so expansion chunks of one item can be
    claimed by several lanes in the same dispatch.

    Subclasses set `t`, `n_shards`, `mesh`, `pack_tiles`, `stats`,
    `_nil_part`, `_buffers` and implement `_merge(b)` (sibling-frontier
    merge fn) and `_lane_step(b)` (the untraced ladder step plus its
    metadata)."""

    def _item(self, b, tile, r, cursor, total):
        return (b, tile, r, cursor, total, self._nil_part)

    def _dead_item(self, item):
        """An all-dead lane filler shaped like `item` (zeros everywhere:
        dead rows/empty partitions contribute nothing by the engine's
        masking invariant)."""
        b, tile, r, _cur, _tot, part = item
        dt, dr, dp = jax.tree.map(jnp.zeros_like, (tile, r, part))
        return (b, dt, dr, 0, 0, dp)

    def _fill_lanes(self, b, stack, pending):
        """Claim up to `n_shards` work items at boundary `b` from the
        global pool; refill idle lanes from the pending slot and by
        chunk-splitting items with multiple expansion chunks remaining
        (the host-side rebalance). Unclaimed chunk remainders go back on
        the stack."""
        S, t = self.n_shards, self.t
        lanes, keep = [], []
        while stack and len(lanes) < S:
            item = stack.pop()
            (lanes if item[0] == b else keep).append(item)
        stack.extend(reversed(keep))
        if len(lanes) < S and b in pending:
            tile_p, r_p, _, tot_p = pending.pop(b)
            lanes.append(self._item(b, tile_p, r_p, 0, tot_p))
            self.stats.shard_rebalances += 1
        for item in list(lanes):
            bb, tile, r, cur, tot, part = item
            while cur + t < tot and len(lanes) < S:
                cur += t
                lanes.append((bb, tile, r, cur, tot, part))
                self.stats.shard_rebalances += 1
            if cur + t < tot:
                stack.append((bb, tile, r, cur + t, tot, part))
        return lanes

    def _push_frontier(self, b, tile, r, alive_n, total, stack, pending):
        """Route a host-resumed frontier: pack sub-capacity frontiers with
        pending siblings at the same boundary (lane-agnostic compaction),
        dispatch-queue otherwise."""
        st = self.stats
        if self.pack_tiles and alive_n * 2 <= self.t:
            pend = pending.get(b)
            if pend is None:
                pending[b] = [tile, r, alive_n, total]
            elif pend[2] + alive_n <= self.t:
                mtile, mr = self._merge(b)(pend[0], pend[1], tile, r)
                st.device_steps += 1
                st.packed_tiles += 1
                pending[b] = [mtile, mr, pend[2] + alive_n, pend[3] + total]
            else:
                stack.append(self._item(b, pend[0], pend[1], 0, pend[3]))
                pending[b] = [tile, r, alive_n, total]
        else:
            stack.append(self._item(b, tile, r, 0, total))

    def _shard_fn(self, b: int, n_aux: int):
        """Cached shard_map-wrapped superstep for boundary `b`: every lane
        runs the same ladder step on its own tile / cursor / partition /
        CER buffers; the `n_aux` trailing step arguments (the table
        groups, or stacked data and active) are replicated; the leaf count
        is psum-reduced across the "data" axis."""
        if not hasattr(self, "_shard_jit"):
            self._shard_jit = {}
        if b in self._shard_jit:
            return self._shard_jit[b]
        step, exit_bounds, seg_cer, seg_fail, n_computes, gather_ops, *_ = \
            self._lane_step(b)

        def body(tile, r, cursor, bufs, fbufs, part, *aux):
            sq = lambda tr: jax.tree.map(lambda x: x[0], tr)  # noqa: E731
            (leaf_tile, terms, cnt, ovf, packed, frontiers, bufs2,
             fbufs2) = step(sq(tile), r[0], cursor[0], sq(bufs), sq(fbufs),
                            *aux, part=part[0])
            total = jax.lax.psum(cnt, "data")
            ex = lambda tr: jax.tree.map(lambda x: x[None], tr)  # noqa: E731
            return (ex(leaf_tile), terms[None], cnt[None], ovf[None],
                    packed[None], ex(frontiers), ex(bufs2), ex(fbufs2),
                    total)

        fn = jax.jit(jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(_SH,) * 6 + (P(),) * n_aux,
            out_specs=(_SH, _SH, _SH, _SH, _SH, _SH, _SH, _SH, P()),
            check_vma=False))
        entry = (fn, exit_bounds, seg_cer, seg_fail, n_computes, gather_ops)
        self._shard_jit[b] = entry
        return entry

    def _dispatch(self, b, lanes, *aux):
        """Pad `lanes` to the mesh width and run one sharded superstep
        *without waiting for its readback*. The CER / failure-cache
        buffers fold forward as asynchronous device values and the
        dispatch-level stats are charged immediately; the host sync is
        deferred to `scheduler._sync_inflight`, which fills the returned
        record's "np" slot from its "sync" tuple. Overlap (dispatching
        superstep N+1 before reading back N) is therefore purely a matter
        of *when* the caller syncs — what is computed never changes."""
        S = self.n_shards
        n_real = len(lanes)
        while len(lanes) < S:
            lanes.append(self._dead_item(lanes[0]))
        (fn, exit_bounds, seg_cer, seg_fail, n_computes,
         gather_ops) = self._shard_fn(b, len(aux))
        args = (_lane_stack([l[1] for l in lanes]),
                jnp.stack([l[2] for l in lanes]),
                np.asarray([l[3] for l in lanes], dtype=np.int32),
                {si: self._buffers[si] for si in seg_cer},
                {si: self._fail_buffers[si] for si in seg_fail},
                jnp.stack([l[5] for l in lanes]), *aux)
        with jax.enable_x64(True):                   # leaf reduce is int64
            outs = fn(*args)
        (leaf_tile, terms, cnt, ovf, packed, frontiers, bufs2, fbufs2,
         total) = outs
        st = self.stats
        _charge_buffers(st, self._n_buffers, b, args, outs)
        for si in seg_cer:
            self._buffers[si] = bufs2[si]
        for si in seg_fail:
            self._fail_buffers[si] = fbufs2[si]
        if self.fail_debug_hook is not None:
            self.fail_debug_hook(self)
        st.device_steps += 1
        st.supersteps += 1
        st.tiles += n_real
        st.expansions += n_real
        st.shard_lanes += n_real
        st.rows_processed += n_real * self.t * max(n_computes, 1)
        st.gather_and_ops += n_real * gather_ops
        return {"n_real": n_real, "exit_bounds": exit_bounds,
                "leaf_tile": leaf_tile, "terms": terms,
                "frontiers": frontiers,
                "sync": (packed, cnt, ovf, total), "np": None}

    def _walk_lane(self, s, row, exit_bounds, frontiers, stack, pending):
        """Apply lane `s`'s packed readback: CER/boundary stats, then
        route the first overflowing frontier back into the pool. Returns
        True when the lane's ladder reached the leaf reduction."""
        st = self.stats
        nb = len(exit_bounds)
        alive_l = [int(v) for v in row[2:2 + nb]]
        total_l = [int(v) for v in row[2 + nb:2 + 2 * nb]]
        tail = [int(v) for v in row[2 + 2 * nb:]]
        st.cer_hits += tail[0]
        st.cer_misses += tail[1]
        st.dedup_keys_seen += tail[2]
        st.dedup_unique += tail[3]
        st.fail_hits += tail[4]
        st.fail_misses += tail[5]
        st.fail_inserts += tail[6]
        st.fail_pruned_rows += tail[7]
        for k in range(nb):
            st.rows_alive += alive_l[k]
            if alive_l[k] == 0:                      # dead end
                return False
            if total_l[k] <= self.t:
                continue                             # consumed in-ladder
            ft = _lane_slice(frontiers[k][0], s)
            fr = frontiers[k][1][s]
            self._push_frontier(exit_bounds[k], ft, fr, alive_l[k],
                                total_l[k], stack, pending)
            return False
        st.leaf_tiles += 1
        st.rows_alive += int(row[1])
        return True


class ShardedTileScheduler(_ShardLoopBase, TileScheduler):
    """Data-parallel TileScheduler: the fused superstep loop of one
    VectorEngine spread over a 1-D "data" mesh.

    Counts are identical to the single-device scheduler: the root
    partition is a disjoint cover of the (globally pruned) level-0
    extension, every other mechanism (frontier chunking, compaction, CER,
    leaf counting) operates on lane-local state, and leaf contributions
    are summed by an on-device psum. The stage-at-a-time compat loop
    (`use_cer_buffer=False`) is not sharded and falls back to the
    single-device path.
    """

    def __init__(self, eng, mesh):
        super().__init__(eng)
        self.mesh = mesh
        self.n_shards = int(mesh.devices.size)
        self.pack_tiles = eng.pack_tiles
        S = self.n_shards
        # one independent CER ring buffer per shard per CER-enabled stage
        self._buffers = {
            si: jax.tree.map(lambda x: jnp.stack([x] * S), buf)
            for si, buf in self._buffers.items()}
        # ditto for the failure-reuse negative cache (per-lane ring buffers)
        self._fail_buffers = {
            si: jax.tree.map(lambda x: jnp.stack([x] * S), buf)
            for si, buf in self._fail_buffers.items()}
        plan = eng.plan
        parts, counts = partition_bitmap(
            np.asarray(plan.masks[plan.root_vertex]),
            root_extension_weights(plan), S)
        # the root contained-vertex prune is global: if the whole root
        # extension fails the threshold every partition is dead, otherwise
        # every partition's bits are live work (a partition may hold fewer
        # bits than the threshold — its subtrees still count)
        con0 = max(len(eng.an.con[0]), 1) if eng.use_cv else 1
        root_alive = int(counts.sum()) >= con0
        self._parts_j = [jnp.asarray(p) for p in parts]
        self._part_counts = [int(c) if root_alive else 0 for c in counts]
        self._nil_part = jnp.zeros((plan.root_words,), jnp.uint32)
        # replicate the packed tables and masks (one buffer per word
        # width) across the mesh once — without this every dispatch would
        # re-broadcast them; the ring records above are lane-stacked
        self._groups = jax.device_put(eng.packed.groups,
                                      NamedSharding(mesh, P()))

    def _merge(self, b: int):
        return self._merge_fn(b)

    def _lane_step(self, b: int):
        return self._build_step(b)

    def run(self, *, limit: int = 1_000_000, max_steps: int | None = None,
            materialize: bool = False) -> VectorMatchResult:
        """Drain the sharded work pool to completion (or `limit`
        embeddings / `max_steps` dispatches). Returns a VectorMatchResult
        with counts identical to the single-device scheduler."""
        if not self.eng.use_cer_buffer:
            # the stage-at-a-time compat loop stays single-device
            return self._run_tiles(limit=limit, max_steps=max_steps,
                                   materialize=materialize)
        eng = self.eng
        st = self.stats = eng.stats = VectorStats()
        S = self.n_shards
        count = 0
        timed_out = False
        embeddings: list[dict[int, int]] = []

        root_tile = {"idx": jnp.zeros((1, 0), jnp.int32), "bm": {},
                     "alive": jnp.ones((1,), bool)}
        root_r = jnp.zeros((1, eng.plan.root_words), jnp.uint32)
        # one root item per non-empty partition; empty partitions (more
        # shards than root candidates) produce no work at all
        stack: list = [
            (0, root_tile, root_r, 0, self._part_counts[s], self._parts_j[s])
            for s in range(S) if self._part_counts[s] > 0]
        pending: dict[int, list] = {}

        def consume(rec):
            """Fold one synced superstep record into the count."""
            packed_np, cnt_np, ovf_np, total_np = rec["np"]
            leaf_tile, terms = rec["leaf_tile"], rec["terms"]
            any_ovf = bool(np.asarray(ovf_np).any())
            lane_sum = 0
            for s in range(rec["n_real"]):
                if not self._walk_lane(s, packed_np[s], rec["exit_bounds"],
                                       rec["frontiers"], stack, pending):
                    continue
                if bool(ovf_np[s]):
                    st.leaf_overflows += 1
                    c = leaf_count_host(eng.plan.leaf_singles,
                                        eng.plan.leaf_groups,
                                        np.asarray(terms[s]),
                                        np.asarray(leaf_tile["alive"][s]))
                else:
                    c = int(cnt_np[s])
                if materialize and c:
                    embeddings.extend(
                        eng._materialize(_lane_slice(leaf_tile, s),
                                         limit - len(embeddings)))
                lane_sum += c
            # psum total is the primary count; the per-lane walk replaces
            # it only when a shard tripped the exact host fallback
            return lane_sum if any_ovf else int(total_np)

        overlap = eng.overlap
        while stack or pending:
            if not stack:
                b = max(pending)                     # flush deepest first
                tile_p, r_p, _, tot_p = pending.pop(b)
                stack.append(self._item(b, tile_p, r_p, 0, tot_p))
                continue
            if max_steps is not None and st.device_steps >= max_steps:
                timed_out = True
                break
            st.peak_stack = max(st.peak_stack, len(stack) + len(pending))
            # double-buffered claim of up to two supersteps; claim and
            # dispatch order is identical for overlap on/off — only the
            # readback timing differs (see scheduler._sync_inflight)
            b = stack[-1][0]
            first = self._dispatch(b, self._fill_lanes(b, stack, pending),
                                   self._groups)
            if not overlap:
                _sync_inflight(st, [first])
            inflight = [first]
            if stack and (max_steps is None
                          or st.device_steps < max_steps):
                b2 = stack[-1][0]
                second = self._dispatch(
                    b2, self._fill_lanes(b2, stack, pending), self._groups)
                if not overlap:
                    _sync_inflight(st, [second])
                inflight.append(second)
            if overlap:
                _sync_inflight(st, inflight)
            for rec in inflight:
                count += consume(rec)
                if count >= limit:
                    break
            if count >= limit:
                break

        return VectorMatchResult(count=min(count, limit), stats=st,
                                 timed_out=timed_out,
                                 embeddings=embeddings if materialize
                                 else None)


class ShardedSuperbatchScheduler(_ShardLoopBase, SuperbatchScheduler):
    """Cross-query superbatch scheduler spread over a 1-D "data" mesh: the
    query-id lane composes with the shard axis.

    Every query's root candidate bitmap is partitioned per shard
    (degree-weighted per query, pruned globally per query), mixed-query
    tiles advance through shard_map-wrapped BatchProgram supersteps with
    per-lane CER ring buffers, and the per-query leaf segment-sums are
    psum-reduced across the mesh. Per-query counts are identical to the
    unsharded SuperbatchScheduler (and therefore to the sequential and
    ref paths).
    """

    def __init__(self, plans, *, mesh, **kw):
        super().__init__(plans, **kw)
        self.mesh = mesh
        self.n_shards = S = int(mesh.devices.size)
        self._n_buffers: dict = {}       # boundary -> leaves per call
        self._buffers = {
            si: jax.tree.map(lambda x: jnp.stack([x] * S), buf)
            for si, buf in self._buffers.items()}
        self._fail_buffers = {
            si: jax.tree.map(lambda x: jnp.stack([x] * S), buf)
            for si, buf in self._fail_buffers.items()}
        mask = np.asarray(self.data["mask_root"])            # (Q, W0)
        w_tabs = [np.asarray(v) for k, v in self.data["tables"].items()
                  if k.startswith("0:")]
        nq_pad, w0 = mask.shape
        parts = np.zeros((S, nq_pad, w0), np.uint32)
        counts = np.zeros(S, np.int64)
        if self.program.use_cv:
            con0 = np.asarray(self.data["con"]["0"])
        else:
            con0 = np.ones(nq_pad, np.int32)
        for q in range(nq_pad):
            w = np.ones(32 * w0, np.float64)
            for tab in w_tabs:
                if tab[q].size:
                    w += np.unpackbits(
                        np.ascontiguousarray(tab[q]).view(np.uint8),
                        axis=1).sum(axis=1)
            pq, cq = partition_bitmap(mask[q], w, S)
            parts[:, q] = pq
            # global per-query prune: a query whose whole root extension
            # fails its threshold contributes nothing; otherwise every
            # partition's bits are live work
            if int(cq.sum()) >= max(int(con0[q]), 1):
                counts += cq
        self._parts_j = [jnp.asarray(parts[s]) for s in range(S)]
        self._part_counts = [int(c) for c in counts]
        self._nil_part = jnp.zeros((nq_pad, w0), jnp.uint32)
        # replicate the stacked per-query tables/masks/thresholds across
        # the mesh once — without this every dispatch would re-broadcast
        self.data = jax.device_put(self.data, NamedSharding(mesh, P()))

    def _merge(self, b: int):
        return self.program.merge_fn(b)

    def _lane_step(self, b: int):
        self.program.compiled_supersteps += 1        # fresh trace follows
        return self.program.build_step(b)

    def run(self, *, limit: int = 1_000_000, max_steps: int | None = None):
        """Drain every query in the bucket to completion (or `limit`
        embeddings each / `max_steps` total dispatches). Returns
        (per-query counts, VectorStats, timed_out) with counts identical
        to the unsharded superbatch path."""
        prog = self.program
        st = self.stats = VectorStats()
        st.batched_queries = self.nq
        compiled_before = prog.compiled_supersteps
        S = self.n_shards
        counts = [0] * self.nq
        timed_out = False
        singles = list(prog.leaf[0])
        groups = [list(g) for g in prog.leaf[1]]
        active_np = np.zeros(self.nq_pad, bool)
        active_np[:self.nq] = True
        active = jnp.asarray(active_np)

        root_tile = {"idx": jnp.zeros((self.nq_pad, 0), jnp.int32),
                     "qid": jnp.arange(self.nq_pad, dtype=jnp.int32),
                     "bm": {},
                     "alive": jnp.arange(self.nq_pad) < self.nq}
        root_r = jnp.zeros((self.nq_pad, prog.widths[0]), jnp.uint32)
        stack: list = [
            (0, root_tile, root_r, 0, self._part_counts[s], self._parts_j[s])
            for s in range(S) if self._part_counts[s] > 0]
        pending: dict[int, list] = {}

        def consume(rec):
            """Fold one synced superstep record into the per-query counts."""
            packed_np, cnt_np, ovf_np, total_np = rec["np"]
            leaf_tile, terms = rec["leaf_tile"], rec["terms"]
            any_ovf = bool(np.asarray(ovf_np).any())
            lane_sums = [0] * self.nq
            for s in range(rec["n_real"]):
                if not self._walk_lane(s, packed_np[s], rec["exit_bounds"],
                                       rec["frontiers"], stack, pending):
                    continue
                if bool(np.asarray(ovf_np[s]).any()):
                    # exact host fallback for this shard's tile, per query
                    st.leaf_overflows += 1
                    terms_np = np.asarray(terms[s])
                    alive_np_s = np.asarray(leaf_tile["alive"][s])
                    qid_np = np.asarray(leaf_tile["qid"][s])
                    for qi in range(self.nq):
                        sel = qid_np == qi
                        lane_sums[qi] += leaf_count_host(
                            singles, groups, terms_np[sel], alive_np_s[sel])
                else:
                    for qi in range(self.nq):
                        lane_sums[qi] += int(cnt_np[s][qi])
            for qi in range(self.nq):
                # psum total is the primary count; per-lane sums replace it
                # only when a shard tripped the exact host fallback
                counts[qi] += (lane_sums[qi] if any_ovf
                               else int(total_np[qi]))

        overlap = self.overlap
        while stack or pending:
            if not stack:
                b = max(pending)
                tile_p, r_p, _, tot_p = pending.pop(b)
                stack.append(self._item(b, tile_p, r_p, 0, tot_p))
                continue
            if max_steps is not None and st.device_steps >= max_steps:
                timed_out = True
                break
            st.peak_stack = max(st.peak_stack, len(stack) + len(pending))
            # double-buffered claim of up to two supersteps (same claim
            # discipline for overlap on/off — only readback timing differs)
            b = stack[-1][0]
            first = self._dispatch(b, self._fill_lanes(b, stack, pending),
                                   self.data, active)
            if not overlap:
                _sync_inflight(st, [first])
            inflight = [first]
            if stack and (max_steps is None
                          or st.device_steps < max_steps):
                b2 = stack[-1][0]
                second = self._dispatch(
                    b2, self._fill_lanes(b2, stack, pending),
                    self.data, active)
                if not overlap:
                    _sync_inflight(st, [second])
                inflight.append(second)
            if overlap:
                _sync_inflight(st, inflight)
            stop = False
            for rec in inflight:
                consume(rec)
                if all(c >= limit for c in counts):
                    stop = True
                    break
                done = [qi for qi in range(self.nq)
                        if active_np[qi] and counts[qi] >= limit]
                if done:
                    active_np[done] = False
                    active = jnp.asarray(active_np)
            if stop:
                break

        st.bucket_recompiles = prog.compiled_supersteps - compiled_before
        return [min(c, limit) for c in counts], st, timed_out
