"""Device-resident tile scheduler for the vectorized CEMR engine.

The engine (engine.py) builds the static stage plan and per-stage closures;
this module owns the runtime. Four mechanisms keep the enumeration on-device
and the host loop thin:

  * **Fused supersteps** — the stage list is cut at *boundary* stages (IDX
    stores and decomposes, i.e. wherever set-bit expansion happens). One
    superstep = one jitted call that expands a frontier chunk and then runs
    the *entire remaining ladder of segments*: each boundary's frontier is
    re-expanded in place as long as it fits one chunk (a traced
    `(total <= tile_rows) & alive` mask guards continuation — overshooting
    segments compute on masked-dead rows and contribute zero), down to the
    leaf reduction. A query whose frontiers all fit completes in a single
    dispatch; overflowing frontiers come back to the host work stack with
    their extension bitmaps and re-enter chunked expansion. The host reads
    back one packed int32 stats vector per superstep instead of syncing per
    primitive.

  * **Frontier compaction + tile packing** — an overflowing frontier that
    comes back to the host with few live rows is not dispatched immediately:
    the scheduler parks it per boundary stage and merges sibling frontiers
    (dead rows compacted out, live rows concatenated) until a tile
    approaches `tile_rows`, so the fixed capacity is utilized instead of
    carrying dead lanes.

  * **Cross-tile CER buffer** — the paper's common extension buffer: a
    device-side ring buffer per CER-enabled stage, keyed by the extension
    read-set (BK + same-label IDX columns). Because the extension bitmap is a
    pure function of that read-set, results cached by one tile serve brother
    embeddings in *sibling* tiles popped later from the work stack. Hit/miss
    counters surface in VectorStats.

  * **Failure-reuse negative cache** — the dual ring buffer: read-sets whose
    extension *failed* (empty or under the contained-vertex threshold) are
    recorded with a conflict witness, and matching frontier rows are masked
    dead right after expansion — before any of their subtree is dispatched.
    Same hash-first/exact-verify lookup (collisions only cost recomputes);
    `fail_*` counters surface in VectorStats. See docs/engine.md
    §Failure-reuse negative cache.

  * **On-device leaf counting** — leaf supersteps are traced under scoped
    x64: the inclusion-exclusion product reduces in int64 on device, with a
    float64 magnitude bound tripping an overflow flag; only flagged tiles
    fall back to the exact host big-int path.

The per-tile bucketed CER compute (engine._bucket_compute_fn) survives as a
compat path (`use_dedup=True, use_cer_buffer=False`), running the legacy
stage-at-a-time loop with corrected step accounting.

A fifth mechanism generalizes the other four **across queries**: the
cross-query superbatch (BatchProgram + SuperbatchScheduler, bottom of this
module) buckets compiled plans by canonical shape signature
(plan.plan_shape_signature) and advances every query in a bucket through
shared jitted supersteps — tiles gain a query-id lane, adjacency gathers
route through stacked per-query tables, CER keys are prefixed with the
query id, and leaf counts segment-sum per query on device. See
docs/engine.md §Cross-query superbatching.
"""
from __future__ import annotations

from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from . import bitops
from .engine import VectorMatchResult, VectorStats
from .plan import IDX, LevelOp
from .spans import span

__all__ = ["TileScheduler", "SuperbatchScheduler", "BatchProgram",
           "leaf_count_host", "make_leaf_reduce", "make_leaf_reduce_batched",
           "stack_batch_inputs", "OVERFLOW_LIMIT"]

# Conservative magnitude bound for the on-device int64 leaf reduction: every
# per-row product and the tile sum are bounded by a float64 upper bound; if
# that bound reaches 2**62 (half of int64 range, >> float64 rounding error)
# the tile falls back to exact host arithmetic.
OVERFLOW_LIMIT = float(2 ** 62)


# ---------------------------------------------------------------------------
# leaf counting
# ---------------------------------------------------------------------------

def leaf_count_host(leaf_singles, leaf_groups, terms, alive):
    """Exact inclusion-exclusion leaf count in Python big-int arithmetic —
    the overflow fallback (and the reference for the device reduction)."""
    terms = np.asarray(terms)
    alive = np.asarray(alive)
    per_row = np.ones(terms.shape[0], dtype=object)
    k = 0
    for _u in leaf_singles:
        per_row = per_row * terms[:, k].astype(object)
        k += 1
    for g in leaf_groups:
        if len(g) == 2:
            pa, pb, pab = terms[:, k], terms[:, k + 1], terms[:, k + 2]
            per_row = per_row * (pa.astype(object) * pb - pab)
            k += 3
        else:
            pa, pb, pc = terms[:, k], terms[:, k + 1], terms[:, k + 2]
            pab, pac, pbc = terms[:, k + 3], terms[:, k + 4], terms[:, k + 5]
            pabc = terms[:, k + 6]
            per_row = per_row * (
                pa.astype(object) * pb * pc - pab * pc - pac * pb
                - pbc * pa + 2 * pabc)
            k += 7
    counts = np.where(alive, per_row, 0)
    return int(counts.sum())


def _leaf_products(n_singles, group_sizes):
    """Per-row inclusion-exclusion products for the device leaf reduction:
    terms (T, n) int32 -> (per (T,) int64, bound (T,) float64). `bound` is a
    conservative float64 magnitude bound on `per` (see OVERFLOW_LIMIT)."""

    def products(terms):
        t64 = terms.astype(jnp.int64)
        f64 = terms.astype(jnp.float64)
        per = jnp.ones(terms.shape[0], jnp.int64)
        bound = jnp.ones(terms.shape[0], jnp.float64)
        k = 0
        for _ in range(n_singles):
            per = per * t64[:, k]
            bound = bound * f64[:, k]
            k += 1
        for gs in group_sizes:
            if gs == 2:
                pa, pb, pab = t64[:, k], t64[:, k + 1], t64[:, k + 2]
                per = per * (pa * pb - pab)
                # pab <= pa*pb, so pa*pb bounds the composite and both
                # intermediates
                bound = bound * f64[:, k] * f64[:, k + 1]
                k += 3
            else:
                pa, pb, pc = t64[:, k], t64[:, k + 1], t64[:, k + 2]
                pab, pac, pbc = t64[:, k + 3], t64[:, k + 4], t64[:, k + 5]
                pabc = t64[:, k + 6]
                per = per * (pa * pb * pc - pab * pc - pac * pb
                             - pbc * pa + 2 * pabc)
                # every subtracted term is <= pa*pb*pc; the +2*pabc tail is
                # covered explicitly
                bound = bound * (f64[:, k] * f64[:, k + 1] * f64[:, k + 2]
                                 + 2.0 * f64[:, k + 6])
                k += 7
        return per, bound

    return products


def make_leaf_reduce(leaf_singles, leaf_groups):
    """Device leaf reduction: (terms (T, n) int32, alive (T,) bool) ->
    (count () int64, overflow () bool). Must be traced under
    jax.enable_x64(True)."""
    products = _leaf_products(len(leaf_singles), [len(g) for g in leaf_groups])

    def reduce(terms, alive):
        per, bound = products(terms)
        bound = jnp.where(alive, bound, 0.0)
        overflow = bound.sum() >= OVERFLOW_LIMIT
        count = jnp.where(alive, per, 0).sum()
        return count, overflow

    return reduce


def make_leaf_reduce_batched(leaf_singles, leaf_groups, n_queries):
    """Superbatch leaf reduction with a query-id lane:
    (terms (T, n) int32, alive (T,) bool, qid (T,) int32) ->
    (count (Q,) int64 segment-summed per query, overflow (Q,) bool).
    Must be traced under jax.enable_x64(True)."""
    products = _leaf_products(len(leaf_singles), [len(g) for g in leaf_groups])

    def reduce(terms, alive, qid):
        per, bound = products(terms)
        per = jnp.where(alive, per, 0)
        bound = jnp.where(alive, bound, 0.0)
        count_q = jnp.zeros(n_queries, jnp.int64).at[qid].add(per)
        bound_q = jnp.zeros(n_queries, jnp.float64).at[qid].add(bound)
        return count_q, bound_q >= OVERFLOW_LIMIT

    return reduce


# ---------------------------------------------------------------------------
# cross-tile CER ring buffer
# ---------------------------------------------------------------------------

def _init_cer_buffer(n_slots: int, key_width: int, n_words: int):
    return {
        "keys": jnp.full((n_slots, key_width), -1, jnp.int32),
        "hash": jnp.full((n_slots,), -1, jnp.int32),
        "vals": jnp.zeros((n_slots, n_words), jnp.uint32),
        "pops": jnp.zeros((n_slots,), jnp.int32),
        "valid": jnp.zeros((n_slots,), bool),
        "ptr": jnp.zeros((), jnp.int32),
    }


def _pack_cer(buf):
    """A CER buffer as one uint32 record (n_slots + 1, K + 3 + n_words):
    columns keys (K), hash, pops, valid, then the vals words; the extra
    last row holds `ptr` in column 0. Int32 fields are bit-cast, so
    `_unpack_cer` restores every field exactly."""
    u32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.uint32)  # noqa: E731
    body = jnp.concatenate(
        [u32(buf["keys"]), u32(buf["hash"])[:, None],
         u32(buf["pops"])[:, None], buf["valid"].astype(jnp.uint32)[:, None],
         buf["vals"]], axis=1)
    tail = jnp.zeros((1, body.shape[1]), jnp.uint32).at[0, 0].set(
        u32(buf["ptr"]))
    return jnp.concatenate([body, tail])


def _unpack_cer(rec, key_width: int):
    """The dict `_cer_compute` takes, from a `_pack_cer` record."""
    i32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.int32)  # noqa: E731
    k, body = key_width, rec[:-1]
    return {"keys": i32(body[:, :k]), "hash": i32(body[:, k]),
            "pops": i32(body[:, k + 1]), "valid": body[:, k + 2] != 0,
            "vals": body[:, k + 3:], "ptr": i32(rec[-1, 0])}


def _empty_cer_record(n_slots: int, key_width: int, n_words: int):
    """`_pack_cer(_init_cer_buffer(...))`, built on the host (no eager
    device ops to lower): keys and hash -1, every other field 0."""
    rec = np.zeros((n_slots + 1, key_width + 3 + n_words), np.uint32)
    rec[:-1, :key_width + 1] = np.uint32(0xFFFFFFFF)
    return jnp.asarray(rec)


def _cer_compute(keys, compute, tile, buf):
    """Buffered extension compute for one CER-enabled stage.

    The buffer caches (key = read-set columns, stacked by the caller — the
    superbatch path prepends the query-id lane so reuse never crosses
    queries) -> (R after same-label bit clearing, popcount) *before* any
    aliveness masking, so a value written by one tile is valid for every
    brother row in any sibling tile. Lookup is hash-first — one (T, K) int32
    compare, then exact-key verification of the single candidate slot — so a
    hash collision can only cause a miss (recompute), never a wrong hit.
    `compute` is a zero-argument thunk running the stage's extension compute
    on the whole tile. Returns
    (r, pop, new_buf, (hits, misses, seen, inserted))."""
    alive = tile["alive"]
    h = jnp.zeros(keys.shape[0], jnp.int32)
    for j in range(keys.shape[1]):
        h = h * jnp.int32(1000003) + keys[:, j]          # wraps: fine
    cand = (buf["hash"][None, :] == h[:, None]) & buf["valid"][None, :]
    maybe = cand.any(axis=1)
    hidx = jnp.argmax(cand, axis=1)
    hit = maybe & (buf["keys"][hidx] == keys).all(axis=-1)
    miss = alive & ~hit
    any_miss = miss.any()
    # the extension compute itself is cond-gated: a fully-warm superstep
    # (every live key cached) skips the gather+AND entirely — the CEB claim,
    # one extension computation per brother class — paying only the lookup
    n_words = buf["vals"].shape[1]

    def _compute(_):
        return compute()

    def _skip(_):
        return (jnp.zeros((keys.shape[0], n_words), jnp.uint32),
                jnp.zeros((keys.shape[0],), jnp.int32))

    r_c, pop_c = jax.lax.cond(any_miss, _compute, _skip, None)
    r = jnp.where(hit[:, None], buf["vals"][hidx], r_c)
    pop = jnp.where(hit, buf["pops"][hidx], pop_c)

    # ring-insert one representative per distinct missing key (deduped by
    # hash: a same-tile hash collision just skips an insert). The whole
    # insert — sort, dedup, scatter — is gated behind the same cond.
    n_slots = buf["keys"].shape[0]

    def do_insert(buf):
        order = jnp.lexsort((h, ~miss))                  # miss rows first
        h_s = h[order]
        miss_s = miss[order]
        diff = jnp.concatenate([jnp.ones(1, bool), h_s[1:] != h_s[:-1]])
        first = miss_s & diff
        rank = jnp.cumsum(first.astype(jnp.int32)) - 1
        # cap inserts at buffer capacity so scatter slots are unique per call
        # (duplicate-slot scatters could pair a key with another row's value)
        first_ok = first & (rank < n_slots)
        n_ins = first_ok.sum().astype(jnp.int32)
        slot = jnp.where(first_ok, (buf["ptr"] + rank) % n_slots,
                         n_slots).astype(jnp.int32)      # n_slots = dummy row
        pad_k = jnp.concatenate([buf["keys"],
                                 jnp.zeros((1, keys.shape[1]), jnp.int32)])
        pad_h = jnp.concatenate([buf["hash"], jnp.zeros((1,), jnp.int32)])
        pad_v = jnp.concatenate(
            [buf["vals"], jnp.zeros((1, buf["vals"].shape[1]), jnp.uint32)])
        pad_p = jnp.concatenate([buf["pops"], jnp.zeros((1,), jnp.int32)])
        pad_ok = jnp.concatenate([buf["valid"], jnp.zeros((1,), bool)])
        pad_k = pad_k.at[slot].set(keys[order])
        pad_h = pad_h.at[slot].set(h_s)
        pad_v = pad_v.at[slot].set(r_c[order])
        pad_p = pad_p.at[slot].set(pop_c[order])
        pad_ok = pad_ok.at[slot].set(jnp.ones(slot.shape[0], bool))
        return {"keys": pad_k[:n_slots], "hash": pad_h[:n_slots],
                "vals": pad_v[:n_slots], "pops": pad_p[:n_slots],
                "valid": pad_ok[:n_slots],
                "ptr": ((buf["ptr"] + n_ins) % n_slots).astype(jnp.int32)
                }, n_ins

    new_buf, n_ins = jax.lax.cond(
        any_miss, do_insert, lambda b: (b, jnp.int32(0)), buf)
    stats = ((alive & hit).sum().astype(jnp.int32),
             miss.sum().astype(jnp.int32),
             alive.sum().astype(jnp.int32), n_ins)
    return r, pop, new_buf, stats


# ---------------------------------------------------------------------------
# failure-reuse negative cache (the dual of the CER ring buffer)
# ---------------------------------------------------------------------------
# CER caches *successful* extensions; this buffer caches *failed* ones (Arai
# et al., "Fast Subgraph Matching by Exploiting Search Failures"): read-sets
# whose extension came back empty or under the contained-vertex threshold.
# Because the extension bitmap — and therefore the failure verdict — is a
# pure function of the read-set key, a recorded failure lets every brother
# row in any later tile be masked dead right after expansion, before its
# subtree is ever dispatched. Entries carry a conflict witness
# (stage << 1 | cause) for observability. Lookup is the same
# hash-first/exact-verify scheme as _cer_compute, so a hash collision can
# only cost a recompute, never a wrong prune.


def _init_fail_buffer(n_slots: int, key_width: int):
    """Empty failure ring buffer: keys (S, K) int32, hash (S,), witness
    (S,) int32 (stage << 1 | cause; cause 1 = contained-vertex threshold,
    0 = empty intersection), valid (S,) bool, ptr () int32 ring cursor."""
    return {
        "keys": jnp.full((n_slots, key_width), -1, jnp.int32),
        "hash": jnp.full((n_slots,), -1, jnp.int32),
        "wit": jnp.zeros((n_slots,), jnp.int32),
        "valid": jnp.zeros((n_slots,), bool),
        "ptr": jnp.zeros((), jnp.int32),
    }


def _pack_fail(buf):
    """A failure buffer as one int32 record (n_slots + 1, K + 3): columns
    keys (K), hash, wit, valid; the extra last row holds `ptr` in column
    0."""
    body = jnp.concatenate(
        [buf["keys"], buf["hash"][:, None], buf["wit"][:, None],
         buf["valid"].astype(jnp.int32)[:, None]], axis=1)
    tail = jnp.zeros((1, body.shape[1]), jnp.int32).at[0, 0].set(buf["ptr"])
    return jnp.concatenate([body, tail])


def _unpack_fail(rec):
    """The dict `_fail_lookup` and `_fail_insert` take, from a `_pack_fail`
    record."""
    body = rec[:-1]
    return {"keys": body[:, :-3], "hash": body[:, -3], "wit": body[:, -2],
            "valid": body[:, -1] != 0, "ptr": rec[-1, 0]}


def _empty_fail_record(n_slots: int, key_width: int):
    """`_pack_fail(_init_fail_buffer(...))`, built on the host: keys and
    hash -1, every other field 0."""
    rec = np.zeros((n_slots + 1, key_width + 3), np.int32)
    rec[:-1, :key_width + 1] = -1
    return jnp.asarray(rec)


def _fail_hash(keys):
    """Row-wise fold of the key columns (same polynomial as _cer_compute)."""
    h = jnp.zeros(keys.shape[0], jnp.int32)
    for j in range(keys.shape[1]):
        h = h * jnp.int32(1000003) + keys[:, j]          # wraps: fine
    return h


def _fail_lookup(keys, alive, buf):
    """Known-failure mask for a tile: hash-first candidate slot, then exact
    key verification — a collision or a poisoned entry can only produce a
    miss (the row computes as usual), never a wrong hit. Restricted to
    `alive` rows so dead lanes neither hit nor count as misses. The whole
    probe is cond-gated on the buffer holding any entry at all, so stages
    whose extensions never fail pay one reduction per superstep, not the
    compare/argmax/gather chain."""
    def probe(_):
        h = _fail_hash(keys)
        cand = (buf["hash"][None, :] == h[:, None]) & buf["valid"][None, :]
        maybe = cand.any(axis=1)
        hidx = jnp.argmax(cand, axis=1)
        return alive & maybe & (buf["keys"][hidx] == keys).all(axis=-1)

    return jax.lax.cond(buf["valid"].any(), probe,
                        lambda _: jnp.zeros_like(alive), None)


def _fail_insert(keys, fail, wit, buf):
    """Ring-insert one representative per distinct failing key (deduped by
    hash, capped at capacity — mirrors _cer_compute.do_insert); the whole
    sort/dedup/scatter is cond-gated so failure-free supersteps pay
    nothing. Returns (new_buf, n_inserted)."""
    n_slots = buf["keys"].shape[0]
    h = _fail_hash(keys)

    def do_insert(buf):
        order = jnp.lexsort((h, ~fail))                  # failing rows first
        h_s = h[order]
        fail_s = fail[order]
        diff = jnp.concatenate([jnp.ones(1, bool), h_s[1:] != h_s[:-1]])
        first = fail_s & diff
        rank = jnp.cumsum(first.astype(jnp.int32)) - 1
        first_ok = first & (rank < n_slots)
        n_ins = first_ok.sum().astype(jnp.int32)
        slot = jnp.where(first_ok, (buf["ptr"] + rank) % n_slots,
                         n_slots).astype(jnp.int32)      # n_slots = dummy row
        pad_k = jnp.concatenate([buf["keys"],
                                 jnp.zeros((1, keys.shape[1]), jnp.int32)])
        pad_h = jnp.concatenate([buf["hash"], jnp.zeros((1,), jnp.int32)])
        pad_w = jnp.concatenate([buf["wit"], jnp.zeros((1,), jnp.int32)])
        pad_ok = jnp.concatenate([buf["valid"], jnp.zeros((1,), bool)])
        pad_k = pad_k.at[slot].set(keys[order])
        pad_h = pad_h.at[slot].set(h_s)
        pad_w = pad_w.at[slot].set(wit[order])
        pad_ok = pad_ok.at[slot].set(jnp.ones(slot.shape[0], bool))
        return {"keys": pad_k[:n_slots], "hash": pad_h[:n_slots],
                "wit": pad_w[:n_slots], "valid": pad_ok[:n_slots],
                "ptr": ((buf["ptr"] + n_ins) % n_slots).astype(jnp.int32)
                }, n_ins

    return jax.lax.cond(fail.any(), do_insert,
                        lambda b: (b, jnp.int32(0)), buf)


def _fail_plan(segs, n_bounds_before, fail_seg, slots_of):
    """Static lookup schedule for one ladder: map segment index k to the
    [(stage, dedup slots)] whose failure buffers become checkable right
    after segment k's expansion. A stage is checkable once every key slot
    is an existing idx column (idx width after segment k's expand is
    `n_bounds_before + k + 1` — each boundary appends one column), and is
    looked up exactly once, at the earliest qualifying segment, so a known
    failure kills the subtree as many expansions early as the key allows."""
    fail_by_seg: list = [[] for _ in segs]
    for sj, ks in fail_seg.items():
        slots = list(slots_of(sj))
        k0 = min(ks, max(0, max(slots) - n_bounds_before))
        fail_by_seg[k0].append((sj, slots))
    for entries in fail_by_seg:
        entries.sort()
    return fail_by_seg


def _charge_buffers(st, cache: dict, b, args, outs):
    """Charge one superstep call's flattened input plus output leaves to
    `st.dispatch_buffers`. Their number is fixed per boundary program, so
    it is counted on the program's first call and cached in `cache`."""
    n = cache.get(b)
    if n is None:
        n = cache[b] = len(jax.tree.leaves((args, outs)))
    st.dispatch_buffers += n


def _sync_inflight(st, inflight):
    """Synchronize in-flight superstep dispatches: one `jax.device_get`
    over every record's `sync` tuple — the only host sync point of the
    fused loops. A coalesced readback of N overlapped supersteps counts as
    one `readbacks` and N-1 `overlapped_supersteps`, which is what makes
    `readbacks <= supersteps` the overlap accounting invariant."""
    with span("cemr.readback", st, "span_readback_s"):
        outs = jax.device_get([p["sync"] for p in inflight])
    for p, o in zip(inflight, outs):
        p["np"] = o
    st.readbacks += 1
    st.overlapped_supersteps += len(inflight) - 1


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

class TileScheduler:
    """Runtime for one VectorEngine: fused supersteps over a host work stack,
    with per-boundary pending buffers for tile packing and engine-lifetime
    CER ring buffers (sound across runs: cached values are pure functions of
    the read-set given the engine's fixed tables)."""

    def __init__(self, eng):
        self.eng = eng
        self.t = eng.t
        self._n_stages = len(eng._stages)
        self._jit: dict = {}
        self._cer_stages = [si for si in range(self._n_stages)
                            if self._cer_eligible(si)]
        # ring buffers live between supersteps as one packed record per
        # stage (`_pack_cer` / `_pack_fail`): one device buffer each
        self._buffers = {}
        for si in self._cer_stages:
            op = eng._stages[si][1]
            self._buffers[si] = _empty_cer_record(
                eng.cer_buffer_slots, len(op.dedup_slots), op.n_words)
        self._fail_stages = [si for si in range(self._n_stages)
                             if self._fail_eligible(si)]
        self._fail_buffers = {
            si: _empty_fail_record(eng.failure_cache_slots,
                                   len(eng._stages[si][1].dedup_slots))
            for si in self._fail_stages}
        self._n_buffers: dict = {}       # boundary -> leaves per call
        # test hook: called with the scheduler after every superstep's
        # buffer fold-back (tests corrupt _fail_buffers mid-run through it)
        self.fail_debug_hook = None
        self.stats = VectorStats()

    # ----------------------------------------------------------- static shape
    def _is_boundary(self, si: int) -> bool:
        stage = self.eng._stages[si]
        return stage[0] == "decompose" or stage[1].store == IDX

    def _cer_eligible(self, si: int) -> bool:
        eng = self.eng
        if not (eng.use_dedup and eng.use_cer_buffer):
            return False
        stage = eng._stages[si]
        return (stage[0] == "extend" and bool(stage[1].dedup_slots)
                and bool(stage[1].bk_pairs))

    def _fail_eligible(self, si: int) -> bool:
        # same read-set requirements as CER (the failure verdict must be a
        # pure function of the dedup-slot key), but independent of
        # use_dedup so the negative cache composes with CER off; the fused
        # path (use_cer_buffer) is required because the compat loop has no
        # failure-cache wiring.
        eng = self.eng
        if not (eng.use_failure_cache and eng.use_cer_buffer):
            return False
        stage = eng._stages[si]
        return (stage[0] == "extend" and bool(stage[1].dedup_slots)
                and bool(stage[1].bk_pairs))

    def _segment(self, b: int):
        """BM-store stages fused after boundary `b`, and the exit stage
        (the next boundary, or n_stages = leaf)."""
        bms = []
        si = b + 1
        while si < self._n_stages and not self._is_boundary(si):
            bms.append(si)
            si += 1
        return bms, si

    # ------------------------------------------------------------- superstep
    def _ladder(self, b: int):
        """Segments from boundary `b` down to the leaf:
        [(boundary, bm_stage list, exit stage), ...]; the last exit is
        n_stages (leaf)."""
        segs = []
        si = b
        while True:
            bms, exit_si = self._segment(si)
            segs.append((si, bms, exit_si))
            if exit_si == self._n_stages:
                return segs
            si = exit_si

    def _build_step(self, b: int):
        """Construct the untraced run-to-completion step for boundary `b`:
        expand the given frontier chunk, then keep descending — each deeper
        boundary's frontier is expanded in place while it fits one chunk
        (traced `proceed` mask; overshooting work is masked dead and
        contributes zero) — ending in the leaf reduction. Returns every
        intermediate frontier so the host can resume exactly where the
        ladder stopped.

        Returns (step, exit_bounds, seg_cer, seg_fail, n_computes,
        gather_ops, pad_bytes); `pad_bytes` counts the padded table copies
        the Pallas kernels make in one dispatch. The step is named
        `cemr_superstep_b<b>`, which names its jitted program. It takes
        `(tile, r, cursor, bufs, fbufs, groups)`: the ring buffers as one
        packed record per stage of the ladder (`{stage: record}`, returned
        updated) and the engine's `TablePack.groups`, which it reads
        through `TablePack.view`. An optional trailing `part` bitmap
        (root_words,) is ANDed into the root extension — the sharded
        scheduler's per-shard partition of the level-0 candidate rows;
        `part=None` (the single-device path) leaves the root mask
        untouched."""
        eng = self.eng
        t = self.t
        cer_set = set(self._cer_stages)
        fail_set = set(self._fail_stages)
        segs = self._ladder(b)
        exit_bounds = [exit_si for (_, _, exit_si) in segs[:-1]]
        built = []                                       # per-segment closures
        seg_cer: list = []
        fail_seg: dict = {}               # fail stage -> computing segment
        from repro.kernels.bitmap_intersect import pad_copy_bytes
        gather_ops = 0
        pad_bytes = 0
        pallas = getattr(eng.intersect_fn, "pallas", False)
        n_computes = 0
        for ki, (si, bms, exit_si) in enumerate(segs):
            leaf_i = exit_si == self._n_stages
            chain = []
            for sj in bms + ([] if leaf_i else [exit_si]):
                compute_r, con = eng._make_compute_parts(sj)
                chain.append((sj, eng._stages[sj][1], compute_r, con))
                seg_cer += [sj] if sj in cer_set else []
                if sj in fail_set:
                    fail_seg[sj] = ki
                if eng._stages[sj][0] == "extend":
                    gather_ops += t * max(len(eng._stages[sj][1].bk_pairs), 1)
                n_computes += 1
            # fused expand+intersect+popcount (one Pallas dispatch for the
            # boundary expansion and the first extend of the segment) when
            # the engine runs with intersect="fused" and the pair is
            # eligible; None composes the plain expand + per-stage computes
            fused0 = eng._make_expand_fused(si, chain[0][0]) if chain else None
            # extends with backward pairs call a Pallas kernel: the fused
            # one for the segment's first, the intersect kernel otherwise
            for ci, (sj, op, _, _) in enumerate(chain):
                if (eng._stages[sj][0] == "extend" and op.bk_pairs
                        and (pallas or (ci == 0 and fused0 is not None))):
                    pad_bytes += pad_copy_bytes(
                        eng.packed.shape(f"{u}:{op.vertex}")
                        for (_, u) in op.bk_pairs)
            built.append((eng._make_expand(si), chain, leaf_i, fused0))
        n_bounds_before = sum(1 for j in range(b) if self._is_boundary(j))
        fail_by_seg = _fail_plan(segs, n_bounds_before, fail_seg,
                                 lambda sj: eng._stages[sj][1].dedup_slots)
        seg_fail = sorted(fail_seg)
        leaf_terms = eng._make_leaf_terms()
        leaf_reduce = make_leaf_reduce(eng.plan.leaf_singles,
                                       eng.plan.leaf_groups)
        root = b == 0
        if root:
            root_compute_r, root_con = eng._make_compute_parts(0)

        def run_compute(si, op, compute_r, con, tile, bufs, fbufs, acc, facc,
                        tables, masks, pre=None):
            # `pre` carries the fused expand+intersect kernel's (r, pop) for
            # the segment's first extend; it is the same pure function of
            # the key columns as compute_r, so the CER cache stays sound
            thunk = ((lambda: pre) if pre is not None
                     else (lambda: compute_r(tile, tables, masks)))
            if si in bufs:
                keys = jnp.stack([tile["idx"][:, s] for s in op.dedup_slots],
                                 axis=1)
                r, pop, bufs[si], s = _cer_compute(
                    keys, thunk, tile, bufs[si])
                acc = [a + v for a, v in zip(acc, s)]
            else:
                r, pop = thunk()
            raw_pop = pop                # true popcount for every alive row
            r, pop, ok = eng.finish_compute(tile, r, pop, con)
            if si in fbufs:
                # failure = an alive row whose extension died here. Alive
                # rows always carry the true (CER-cached or computed) pop,
                # and the verdict is a pure function of the key columns,
                # so the entry is sound for every future brother row.
                fkeys = jnp.stack(
                    [tile["idx"][:, s] for s in op.dedup_slots], axis=1)
                failed = tile["alive"] & ~ok
                wit = jnp.int32(2 * si) + (raw_pop > 0).astype(jnp.int32)
                fbufs[si], n_ins = _fail_insert(fkeys, failed, wit,
                                                fbufs[si])
                facc[2] = facc[2] + n_ins
            return r, pop, ok, acc

        def apply_fail_masks(k, cur, fbufs, facc):
            # lookup-and-mask right after segment k's expansion (rank
            # stable: R bit ranks, and therefore host chunk cursors, are
            # untouched). A masked row's exit bitmap is zeroed downstream,
            # so its subtree is never dispatched.
            if not fail_by_seg[k]:
                return
            alive0 = cur["alive"]
            dead = jnp.zeros_like(alive0)
            for (sj, slots) in fail_by_seg[k]:
                fkeys = jnp.stack([cur["idx"][:, s] for s in slots], axis=1)
                fhit = _fail_lookup(fkeys, alive0, fbufs[sj])
                facc[0] = facc[0] + fhit.sum().astype(jnp.int32)
                facc[1] = facc[1] + (alive0 & ~fhit).sum().astype(jnp.int32)
                dead = dead | fhit
            cur["alive"] = alive0 & ~dead
            facc[3] = facc[3] + dead.sum().astype(jnp.int32)

        cer_k = {si: len(eng._stages[si][1].dedup_slots) for si in seg_cer}

        def step(tile, r_in, cursor, bufs, fbufs, groups, part=None):
            tables, masks = eng.packed.view(groups)
            bufs = {si: _unpack_cer(rec, cer_k[si])
                    for si, rec in bufs.items()}
            fbufs = {si: _unpack_fail(rec) for si, rec in fbufs.items()}
            acc = [jnp.int32(0)] * 4                     # hits/misses/seen/ins
            facc = [jnp.int32(0)] * 4                    # fail h/m/ins/pruned
            if root:
                r0, pop0 = root_compute_r(tile, tables, masks)
                r_in, _, _ = eng.finish_compute(tile, r0, pop0, root_con)
                if part is not None:
                    # shard partition of the *pruned* root extension: the
                    # contained-vertex threshold must see the global
                    # popcount, never a partition's (a sub-threshold
                    # partition of a viable root set is still live work)
                    r_in = r_in & part[None, :]
            frontiers = []                               # (tile, r) per bound
            alive_l, total_l = [], []
            proceed = None
            cur_tile, cur_r, cur_cursor = tile, r_in, cursor
            total_in = None
            for k, (expand, chain, leaf_i, fused0) in enumerate(built):
                if fused0 is not None:
                    cur, tot, pre0 = fused0(cur_tile, cur_r, cur_cursor,
                                            tables)
                else:
                    cur, tot = expand(cur_tile, cur_r, cur_cursor, tables)
                    pre0 = None
                if k == 0:
                    total_in = tot.astype(jnp.int32)
                else:
                    cur["alive"] = cur["alive"] & proceed
                apply_fail_masks(k, cur, fbufs, facc)
                last = None
                for ci, (sj, op, compute_r, con) in enumerate(chain):
                    r, pop, ok, acc = run_compute(sj, op, compute_r, con,
                                                  cur, bufs, fbufs, acc,
                                                  facc, tables, masks,
                                                  pre=pre0 if ci == 0
                                                  else None)
                    last = (r, pop, ok)
                    if not leaf_i and sj == chain[-1][0]:
                        break                            # exit compute: no store
                    bm = dict(cur["bm"])
                    bm[op.vertex] = r
                    cur = {"idx": cur["idx"], "bm": bm, "alive": ok}
                if leaf_i:
                    terms = leaf_terms(cur)
                    count, overflow = leaf_reduce(terms, cur["alive"])
                    leaf_alive = cur["alive"].sum().astype(jnp.int32)
                    packed = jnp.stack(
                        [total_in, leaf_alive, *alive_l, *total_l, *acc,
                         *facc])
                    return (cur, terms, count, overflow, packed, frontiers,
                            {si: _pack_cer(v) for si, v in bufs.items()},
                            {si: _pack_fail(v) for si, v in fbufs.items()})
                r2, pop2, ok2 = last
                alive_k = ok2.sum().astype(jnp.int32)
                total_k = jnp.sum(pop2, dtype=jnp.int32)
                frontiers.append((cur, r2))
                alive_l.append(alive_k)
                total_l.append(total_k)
                ok_here = (total_k <= t) & (alive_k > 0)
                proceed = ok_here if proceed is None else (proceed & ok_here)
                cur_tile, cur_r, cur_cursor = cur, r2, jnp.int32(0)

        step.__name__ = step.__qualname__ = f"cemr_superstep_b{b}"
        return (step, exit_bounds, sorted(set(seg_cer)), seg_fail,
                n_computes, gather_ops, pad_bytes)

    def _superstep(self, b: int):
        """Cached jitted wrapper of `_build_step(b)` — one device dispatch
        per call on the single-device path."""
        key = ("ss", b)
        if key in self._jit:
            return self._jit[key]
        step, *static = self._build_step(b)
        entry = (jax.jit(step), *static)
        self._jit[key] = entry
        return entry

    def _merge_fn(self, b: int):
        """Frontier compaction: concatenate two sub-capacity sibling
        frontiers at boundary `b`, live rows (nonzero extension bitmap)
        packed to the front, sliced back to tile capacity."""
        key = ("merge", b)
        if key in self._jit:
            return self._jit[key]
        t = self.t

        def cemr_merge(ta, ra, tb, rb):
            idx = jnp.concatenate([ta["idx"], tb["idx"]])
            bm = {u: jnp.concatenate([ta["bm"][u], tb["bm"][u]])
                  for u in ta["bm"]}
            r = jnp.concatenate([ra, rb])
            live = bitops.row_popcount(r) > 0
            order = jnp.argsort(~live)[:t]               # stable: live first
            tile = {"idx": idx[order],
                    "bm": {u: c[order] for u, c in bm.items()},
                    "alive": live[order]}
            return tile, r[order]

        fn = jax.jit(cemr_merge)
        self._jit[key] = fn
        return fn

    # ------------------------------------------------------------------- run
    def run(self, *, limit: int = 1_000_000, max_steps: int | None = None,
            materialize: bool = False) -> VectorMatchResult:
        """Enumerate to completion (or until `limit` embeddings /
        `max_steps` jitted dispatches, whichever first). Returns a
        VectorMatchResult; `materialize=True` additionally decodes explicit
        embeddings from every counted leaf tile."""
        # use_cer_buffer=False selects the stage-at-a-time compat loop (the
        # documented legacy architecture), with or without its per-tile
        # bucketed CER (use_dedup)
        if not self.eng.use_cer_buffer:
            return self._run_tiles(limit=limit, max_steps=max_steps,
                                   materialize=materialize)
        return self._run_fused(limit=limit, max_steps=max_steps,
                               materialize=materialize)

    def _push_frontier(self, b, tile, r, alive_n, total, stack, pending):
        """Route a host-resumed frontier: pack sub-capacity frontiers with
        pending siblings at the same boundary, dispatch otherwise."""
        st = self.stats
        if self.eng.pack_tiles and alive_n * 2 <= self.t:
            pend = pending.get(b)
            if pend is None:
                pending[b] = [tile, r, alive_n, total]
            elif pend[2] + alive_n <= self.t:
                mtile, mr = self._merge_fn(b)(pend[0], pend[1], tile, r)
                st.device_steps += 1
                st.packed_tiles += 1
                pending[b] = [mtile, mr, pend[2] + alive_n, pend[3] + total]
            else:
                stack.append((b, pend[0], pend[1], 0, pend[3]))
                pending[b] = [tile, r, alive_n, total]
        else:
            stack.append((b, tile, r, 0, total))

    def _dispatch_fused(self, item, stack):
        """Issue one fused superstep without waiting for its readback. The
        CER/failure ring buffers fold forward as asynchronous device arrays
        (no sync needed — only the packed stats parse does), dispatch-side
        stats are charged immediately, and an item with a known bit total
        re-enqueues its next expansion chunk right away, so the work-pool
        refill decision never sits on the readback critical path. Returns
        the in-flight record for `_sync_inflight`."""
        eng = self.eng
        st = self.stats
        b, tile, r, cursor, tot = item
        with span("cemr.dispatch", st, "span_dispatch_s", boundary=b):
            (fn, exit_bounds, seg_cer, seg_fail, n_computes, gather_ops,
             pad_bytes) = self._superstep(b)
            # the cursor goes in as a host scalar: no eager device upload
            args = (tile, r, np.int32(cursor),
                    {si: self._buffers[si] for si in seg_cer},
                    {si: self._fail_buffers[si] for si in seg_fail},
                    eng.packed.groups)
            with jax.enable_x64(True):               # leaf reduce is int64
                outs = fn(*args)
            (leaf_tile, terms, cnt, ovf, packed, frontiers, bufs2,
             fbufs2) = outs
            _charge_buffers(st, self._n_buffers, b, args, outs)
            for si in seg_cer:
                self._buffers[si] = bufs2[si]
            for si in seg_fail:
                self._fail_buffers[si] = fbufs2[si]
            if self.fail_debug_hook is not None:
                self.fail_debug_hook(self)
            st.device_steps += 1
            st.supersteps += 1
            st.tiles += 1
            st.expansions += 1
            st.rows_processed += self.t * max(n_computes, 1)
            st.gather_and_ops += gather_ops
            st.pad_copy_bytes += pad_bytes
            if tot >= 0 and cursor + self.t < tot:
                stack.append((b, tile, r, cursor + self.t, tot))
            return {"item": item, "exit_bounds": exit_bounds,
                    "leaf_tile": leaf_tile, "terms": terms,
                    "frontiers": frontiers, "sync": (packed, cnt, ovf),
                    "np": None}

    def _process_fused(self, p, stack, pending, embeddings, materialize,
                       limit):
        """Apply one synced readback: fold the packed tail counters, resume
        the root chunk cursor (the only item whose total is unknown at
        dispatch), walk the ladder routing the first overflowing frontier,
        and return the leaf count (exact host fallback on overflow)."""
        eng = self.eng
        st = self.stats
        t = self.t
        b, tile, r, cursor, tot = p["item"]
        packed_np, cnt_np, ovf_np = p["np"]
        exit_bounds = p["exit_bounds"]
        nb = len(exit_bounds)
        total_in = int(packed_np[0])
        leaf_alive = int(packed_np[1])
        alive_l = [int(v) for v in packed_np[2:2 + nb]]
        total_l = [int(v) for v in packed_np[2 + nb:2 + 2 * nb]]
        tail = [int(v) for v in packed_np[2 + 2 * nb:]]
        st.cer_hits += tail[0]
        st.cer_misses += tail[1]
        st.dedup_keys_seen += tail[2]
        st.dedup_unique += tail[3]
        st.fail_hits += tail[4]
        st.fail_misses += tail[5]
        st.fail_inserts += tail[6]
        st.fail_pruned_rows += tail[7]
        if tot < 0 and cursor + t < total_in:
            stack.append((b, tile, r, cursor + t, total_in))
        # walk the ladder: consumed boundaries (single-chunk) descend
        # in-device; the first overflowing frontier resumes on the host
        for k in range(nb):
            st.rows_alive += alive_l[k]
            if alive_l[k] == 0:                      # dead end
                return 0
            if total_l[k] <= t:
                continue                             # consumed in-ladder
            ft, fr = p["frontiers"][k]
            self._push_frontier(exit_bounds[k], ft, fr, alive_l[k],
                                total_l[k], stack, pending)
            return 0
        st.leaf_tiles += 1
        st.rows_alive += leaf_alive
        if bool(ovf_np):
            st.leaf_overflows += 1
            c = leaf_count_host(eng.plan.leaf_singles, eng.plan.leaf_groups,
                                p["terms"], p["leaf_tile"]["alive"])
        else:
            c = int(cnt_np)
        if materialize and c:
            embeddings.extend(eng._materialize(p["leaf_tile"],
                                               limit - len(embeddings)))
        return c

    def _run_fused(self, *, limit, max_steps, materialize):
        eng = self.eng
        st = self.stats = eng.stats = VectorStats()
        count = 0
        timed_out = False
        embeddings: list[dict[int, int]] = []

        root_tile = {"idx": jnp.zeros((1, 0), jnp.int32), "bm": {},
                     "alive": jnp.ones((1,), bool)}
        root_r = jnp.zeros((1, eng.plan.root_words), jnp.uint32)  # recomputed
        # frontier items: (boundary stage, tile, extension bitmap R, cursor,
        # total set bits of R — or -1 for the root item, whose extension is
        # only computed in-dispatch)
        stack: list = [(0, root_tile, root_r, 0, -1)]
        # boundary -> [tile, r, live rows, total bits]: sub-capacity frontiers
        # waiting to be packed with siblings
        pending: dict[int, list] = {}

        while stack or pending:
            if not stack:
                b = max(pending)                         # flush deepest first
                tile_p, r_p, _, tot_p = pending.pop(b)
                stack.append((b, tile_p, r_p, 0, tot_p))
                continue
            if max_steps is not None and st.device_steps >= max_steps:
                timed_out = True
                break
            st.peak_stack = max(st.peak_stack, len(stack) + len(pending))
            # Claim and dispatch up to two items per round (double-buffered
            # frontiers). The claim discipline is identical for overlap
            # on/off — overlap only defers/coalesces the device_get — so
            # both settings run the same superstep sequence against the
            # same buffer states: bit-identical counts and stats by
            # construction (modulo the readback counters themselves).
            first = self._dispatch_fused(stack.pop(), stack)
            if not eng.overlap:
                _sync_inflight(st, [first])
            inflight = [first]
            if stack and (max_steps is None
                          or st.device_steps < max_steps):
                second = self._dispatch_fused(stack.pop(), stack)
                if not eng.overlap:
                    _sync_inflight(st, [second])
                inflight.append(second)
            if eng.overlap:
                _sync_inflight(st, inflight)
            for p in inflight:
                with span("cemr.process"):
                    count += self._process_fused(p, stack, pending,
                                                 embeddings, materialize,
                                                 limit)
                if count >= limit:
                    break
            if count >= limit:
                break

        return VectorMatchResult(count=min(count, limit), stats=st,
                                 timed_out=timed_out,
                                 embeddings=embeddings if materialize else None)

    # ---------------------------------------------------------- compat path
    def _leaf_reduce_fn(self):
        key = ("leaf_reduce",)
        if key in self._jit:
            return self._jit[key]
        fn = jax.jit(make_leaf_reduce(self.eng.plan.leaf_singles,
                                      self.eng.plan.leaf_groups))
        self._jit[key] = fn
        return fn

    def _leaf_count(self, tile):
        """Device uint64 leaf count with exact host fallback on overflow."""
        st = self.stats
        eng = self.eng
        terms, alive = eng._leaf_fn()(tile)
        st.device_steps += 1
        with jax.enable_x64(True):
            cnt, ovf = self._leaf_reduce_fn()(terms, alive)
        st.device_steps += 1
        if bool(jax.device_get(ovf)):
            st.leaf_overflows += 1
            return leaf_count_host(eng.plan.leaf_singles, eng.plan.leaf_groups,
                                   terms, alive)
        return int(jax.device_get(cnt))

    def _run_tiles(self, *, limit, max_steps, materialize):
        """Stage-at-a-time loop (pre-superstep architecture): one jitted
        dispatch per primitive with host-driven control flow. Kept as the
        `use_cer_buffer=False` compat path — it is where the per-tile CER
        bucketed compute lives — and as a parity reference for the fused
        scheduler. Each dispatch charges `device_steps` exactly once."""
        eng = self.eng
        st = self.stats = eng.stats = VectorStats()
        t = self.t
        n_stages = self._n_stages
        count = 0
        timed_out = False
        embeddings: list[dict[int, int]] = []

        root_tile = {"idx": jnp.zeros((1, 0), jnp.int32), "bm": {},
                     "alive": jnp.ones((1,), bool)}
        # stack: ("tile", stage, tile) | ("expand", stage, tile, R, cursor)
        stack: list = [("tile", 0, root_tile)]

        while stack:
            if max_steps is not None and st.device_steps >= max_steps:
                timed_out = True
                break
            st.peak_stack = max(st.peak_stack, len(stack))
            item = stack.pop()
            if item[0] == "tile":
                _, si, tile = item
                if si == n_stages:           # leaf
                    st.leaf_tiles += 1
                    c = self._leaf_count(tile)
                    if materialize and c:
                        embeddings.extend(eng._materialize(
                            tile, limit - len(embeddings)))
                    count += c
                    if count >= limit:
                        break
                    continue
                stage = eng._stages[si]
                st.tiles += 1
                rows = int(tile["alive"].shape[0])
                st.rows_processed += rows
                if stage[0] == "decompose":
                    r, ok = eng._compute_fn(si)(tile, eng.packed.groups)
                    st.device_steps += 1
                    stack.append(("expand", si, tile, r, 0))
                else:
                    op: LevelOp = stage[1]
                    bucketed = False
                    if eng.use_dedup and op.dedup_slots and op.bk_pairs:
                        u, rep_rows, group_of = eng._dedup_fn(si)(tile)
                        st.device_steps += 1
                        u = int(u)
                        st.dedup_keys_seen += int(
                            np.asarray(tile["alive"]).sum())
                        st.dedup_unique += u
                        if 0 < u <= rows // 2:
                            # CER: one extension compute per brother class
                            bucket = 1 << max(u - 1, 1).bit_length()
                            bucket = min(bucket, rows)
                            r, ok = eng._bucket_compute_fn(si, bucket)(
                                tile, rep_rows, group_of, eng.packed.groups)
                            st.device_steps += 1
                            st.bucketed_tiles += 1
                            st.gather_and_ops += bucket * len(op.bk_pairs)
                            bucketed = True
                    if not bucketed:
                        st.gather_and_ops += rows * max(len(op.bk_pairs), 1)
                        r, ok = eng._compute_fn(si)(tile,
                                                    eng.packed.groups)
                        st.device_steps += 1
                    if op.store == IDX:
                        stack.append(("expand", si, tile, r, 0))
                    else:
                        bm = dict(tile["bm"])
                        bm[op.vertex] = r
                        new_tile = {"idx": tile["idx"], "bm": bm, "alive": ok}
                        if bool(jnp.any(ok)):
                            stack.append(("tile", si + 1, new_tile))
            else:
                _, si, tile, r, cursor = item
                st.expansions += 1
                out, total = eng._expand_fn(si)(tile, r, np.int32(cursor),
                                                eng.packed.groups)
                st.device_steps += 1
                total = int(total)
                if cursor + t < total:
                    stack.append(("expand", si, tile, r, cursor + t))
                alive_n = int(np.asarray(out["alive"]).sum())
                st.rows_alive += alive_n
                if alive_n:
                    stack.append(("tile", si + 1, out))

        return VectorMatchResult(count=min(count, limit), stats=st,
                                 timed_out=timed_out,
                                 embeddings=embeddings if materialize else None)


# ---------------------------------------------------------------------------
# cross-query superbatch
# ---------------------------------------------------------------------------
# `Matcher.match_many(batch="auto")` buckets compiled plans by
# `plan.plan_shape_signature` (vertices renamed to their match level, bitmap
# widths padded to powers of two) and drains each bucket through one
# SuperbatchScheduler: tiles gain a query-id lane, every adjacency gather
# routes through stacked per-query tables, CER keys are prefixed with the
# query id (reuse never crosses queries), and the leaf reduction
# segment-sums counts per query on device. One BatchProgram — and therefore
# one set of jitted supersteps — serves every bucket that shares a
# signature, so recompiles are bounded by the number of distinct padded
# shapes in the workload, not by the number of queries.


def _canon_inverse(plan) -> dict[int, int]:
    """Canonical vertex id (match level) -> original query vertex id."""
    inv = {0: plan.root_vertex}
    for op in plan.ops:
        inv[op.level] = op.vertex
    return inv


def _batch_table_keys(sig) -> list[tuple[int, int]]:
    """Canonical (src, dst) adjacency-table keys the program gathers from."""
    keys = set()
    for stage in sig[3]:
        if stage[0] != "e":
            continue
        v, bk, wt, union_src = stage[1], stage[3], stage[4], stage[5]
        for (_s, u) in bk:
            keys.add((u, v))
        for u_j in wt:
            keys.add((v, u_j))
        if not bk and union_src >= 0:
            keys.add((union_src, v))
    return sorted(keys)


def stack_batch_inputs(sig, plans, n_queries):
    """Stack per-query plan data into the padded device arrays a BatchProgram
    consumes: adjacency tables (Q, 32*Wp(src), Wp(dst)), the root candidate
    mask (Q, Wp(root)), and per-stage contained-vertex thresholds (Q,).
    Zero-padding is inert everywhere — padded table rows/words carry no set
    bits and padded queries (len(plans) <= n_queries) get no root candidates."""
    widths, stages = sig[2], sig[3]
    invs = [_canon_inverse(p) for p in plans]
    tabs = {}
    for (cu, cv) in _batch_table_keys(sig):
        arr = np.zeros((n_queries, 32 * widths[cu], widths[cv]), np.uint32)
        for qi, plan in enumerate(plans):
            t = plan.tables[(invs[qi][cu], invs[qi][cv])]
            arr[qi, :t.shape[0], :t.shape[1]] = t
        tabs[f"{cu}:{cv}"] = jnp.asarray(arr)
    mask = np.zeros((n_queries, widths[0]), np.uint32)
    for qi, plan in enumerate(plans):
        m = plan.masks[plan.root_vertex]
        mask[qi, :m.shape[0]] = m
    con = {}
    for si, stage in enumerate(stages):
        if stage[0] == "d":
            continue
        if stage[0] == "root":
            vals = [len(p.an.con[0]) for p in plans]
        else:
            lvl = stage[1]
            vals = [next(op.con_threshold for op in p.ops if op.level == lvl)
                    for p in plans]
        a = np.ones(n_queries, np.int32)
        a[:len(plans)] = np.maximum(vals, 1)
        con[str(si)] = jnp.asarray(a)
    return {"tables": tabs, "mask_root": jnp.asarray(mask), "con": con}


def _union_rows_batched(tables, bmcol, qid):
    """Batched no-black-bwd union: OR of adjacency rows selected by a bitmap
    column, with row t reading query qid[t]'s table. tables (Q, S, W) where
    S = 32 * (bmcol words). Unlike the single-query _union_rows (a boolean
    matmul over unpacked bits), this stays in packed uint32 — masked rows
    OR-reduced over the source axis — because unpacking a per-row gathered
    (T, S, 32W) bit tensor would blow up memory for wide spaces."""
    s = tables.shape[1]
    word = jnp.arange(s, dtype=jnp.int32) >> 5
    bit = (jnp.arange(s, dtype=jnp.int32) & 31).astype(jnp.uint32)
    src = ((bmcol[:, word] >> bit[None, :]) & jnp.uint32(1)) != 0    # (T, S)
    sel = jnp.where(src[:, :, None], tables[qid], jnp.uint32(0))     # (T, S, W)
    return jax.lax.reduce(sel, np.uint32(0), jax.lax.bitwise_or, (1,))


class BatchProgram:
    """Batched (query-id lane) stage closures for one canonical plan shape
    signature. Built from the signature alone — no per-query data — so one
    program and its jitted supersteps serve every plan bucket sharing the
    signature; per-query tables/masks/thresholds arrive as the stacked
    `data` argument (stack_batch_inputs). Mirrors VectorEngine's closures
    with three changes: every adjacency gather indexes `tables[key][qid,
    idx]`, contained-vertex thresholds are per-row data, and the leaf
    reduction segment-sums per query."""

    def __init__(self, sig, n_queries, *, use_cv=True, use_cer=True,
                 use_fail=True):
        self.sig = sig
        _, self.t, self.widths, self._stages, self.leaf = sig
        self.nq = n_queries
        self.use_cv = use_cv
        self.use_cer = use_cer
        self.use_fail = use_fail
        self._n_stages = len(self._stages)
        self._jit: dict = {}
        self.compiled_supersteps = 0      # fresh jit traces (bucket_recompiles)
        self._cer_stages = [si for si, stg in enumerate(self._stages)
                            if use_cer and stg[0] == "e" and stg[8] and stg[3]]
        # failure-cache stages: same read-set requirements as CER, gated by
        # its own knob (keys are qid-prefixed, like CER, so a recorded
        # failure never crosses queries)
        self._fail_stages = [si for si, stg in enumerate(self._stages)
                             if use_fail and stg[0] == "e" and stg[8]
                             and stg[3]]

    # ----------------------------------------------------------- static shape
    def dedup_slots(self, si: int) -> tuple:
        """CER dedup-key idx slots of stage `si` (empty = CER-ineligible)."""
        stg = self._stages[si]
        return stg[8] if stg[0] == "e" else ()

    def stage_width(self, si: int) -> int:
        """Padded bitmap words of the stage's extension target."""
        stg = self._stages[si]
        return self.widths[0] if stg[0] == "root" else self.widths[stg[1]]

    def _is_boundary(self, si: int) -> bool:
        stg = self._stages[si]
        return stg[0] in ("root", "d") or stg[2] == IDX

    def _segment(self, b: int):
        bms = []
        si = b + 1
        while si < self._n_stages and not self._is_boundary(si):
            bms.append(si)
            si += 1
        return bms, si

    def _ladder(self, b: int):
        segs = []
        si = b
        while True:
            bms, exit_si = self._segment(si)
            segs.append((si, bms, exit_si))
            if exit_si == self._n_stages:
                return segs
            si = exit_si

    # ----------------------------------------------------------- raw closures
    def _make_compute_parts(self, si: int):
        """(compute_r(tile, data) -> (r, pop), con_key): the batched analogue
        of VectorEngine._make_compute_parts. con_key indexes data["con"]
        (per-query thresholds); None means no contained-vertex prune."""
        stage = self._stages[si]
        if stage[0] == "root":

            def compute_r(tile, data):
                r = data["mask_root"][tile["qid"]]
                return r, bitops.row_popcount(r)

            return compute_r, (str(si) if self.use_cv else None)
        if stage[0] == "d":
            v = stage[1]

            def compute_r(tile, data):
                r = tile["bm"][v]
                return r, bitops.row_popcount(r)

            return compute_r, None
        v, bk, union_src, same_idx = stage[1], stage[3], stage[5], stage[6]

        def compute_r(tile, data):
            qid = tile["qid"]
            if bk:
                r = None
                for (s, u) in bk:
                    rows = data["tables"][f"{u}:{v}"][qid, tile["idx"][:, s]]
                    r = rows if r is None else (r & rows)
            else:
                r = _union_rows_batched(data["tables"][f"{union_src}:{v}"],
                                        tile["bm"][union_src], qid)
            for s in same_idx:
                r = bitops.clear_bit_rows(r, tile["idx"][:, s])
            return r, bitops.row_popcount(r)

        return compute_r, (str(si) if self.use_cv else None)

    def _finish(self, tile, r, pop, con_key, data):
        con = (data["con"][con_key][tile["qid"]]
               if con_key is not None else 1)
        ok = tile["alive"] & (pop >= con) & (pop > 0)
        r = jnp.where(ok[:, None], r, jnp.uint32(0))
        pop = jnp.where(ok, pop, 0)
        return r, pop, ok

    def _make_expand(self, si: int):
        stage = self._stages[si]
        t_out = self.t
        if stage[0] == "d":
            wt_prune: list[tuple[int, str]] = []
            same_label_bm = list(stage[3])
            drop_bm = stage[1]
        elif stage[0] == "root":
            wt_prune, same_label_bm, drop_bm = [], [], None
        else:
            v, wt = stage[1], stage[4]
            wt_prune = [(u_j, f"{v}:{u_j}") for u_j in wt]
            same_label_bm = list(stage[7])
            drop_bm = None

        def expand(tile, r, start, data):
            rows, bitpos, valid, total = bitops.expand_select(r, start, t_out)
            idx = tile["idx"][rows]
            idx = jnp.concatenate([idx, bitpos[:, None]], axis=1)
            qid = tile["qid"][rows]
            bm_out = {}
            alive = valid
            for u, col in tile["bm"].items():
                if u == drop_bm:
                    continue
                g = col[rows]
                for (u_j, tkey) in wt_prune:
                    if u_j == u:
                        g = g & data["tables"][tkey][qid, bitpos]
                if u in same_label_bm:
                    g = bitops.clear_bit_rows(g, bitpos)
                alive = alive & (bitops.row_popcount(g) > 0)
                bm_out[u] = g
            return {"idx": idx, "qid": qid, "bm": bm_out,
                    "alive": alive}, total

        return expand

    def _make_leaf_terms(self):
        singles = list(self.leaf[0])
        groups = [list(g) for g in self.leaf[1]]

        def leaf(tile):
            terms = []
            for u in singles:
                terms.append(bitops.row_popcount(tile["bm"][u]))
            for g in groups:
                if len(g) == 2:
                    a, b = tile["bm"][g[0]], tile["bm"][g[1]]
                    terms += [bitops.row_popcount(a), bitops.row_popcount(b),
                              bitops.row_popcount(a & b)]
                else:
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

    # ------------------------------------------------------------- superstep
    def build_step(self, b: int):
        """Construct the untraced batched run-to-completion step for
        boundary `b` — the query-id-lane mirror of
        `TileScheduler._build_step`.

        Returns (step, exit_bounds, seg_cer, seg_fail, n_computes,
        gather_ops). The step's optional trailing `part` bitmap
        (n_queries, root_words) is ANDed per query into the root extension
        — the sharded scheduler's per-shard partition of every query's
        level-0 candidate rows; `part=None` (single-device) leaves the
        root masks untouched."""
        t = self.t
        cer_set = set(self._cer_stages)
        fail_set = set(self._fail_stages)
        segs = self._ladder(b)
        exit_bounds = [exit_si for (_, _, exit_si) in segs[:-1]]
        built = []
        seg_cer: list = []
        fail_seg: dict = {}               # fail stage -> computing segment
        gather_ops = 0
        n_computes = 0
        for ki, (si, bms, exit_si) in enumerate(segs):
            leaf_i = exit_si == self._n_stages
            chain = []
            for sj in bms + ([] if leaf_i else [exit_si]):
                compute_r, con_key = self._make_compute_parts(sj)
                chain.append((sj, self.dedup_slots(sj), compute_r, con_key))
                seg_cer += [sj] if sj in cer_set else []
                if sj in fail_set:
                    fail_seg[sj] = ki
                if self._stages[sj][0] == "e":
                    gather_ops += t * max(len(self._stages[sj][3]), 1)
                n_computes += 1
            built.append((self._make_expand(si), chain, leaf_i))
        n_bounds_before = sum(1 for j in range(b) if self._is_boundary(j))
        fail_by_seg = _fail_plan(segs, n_bounds_before, fail_seg,
                                 self.dedup_slots)
        seg_fail = sorted(fail_seg)
        leaf_terms = self._make_leaf_terms()
        leaf_reduce = make_leaf_reduce_batched(
            list(self.leaf[0]), [list(g) for g in self.leaf[1]], self.nq)
        root = b == 0
        if root:
            root_compute_r, root_con = self._make_compute_parts(0)

        def run_compute(si, dedup, compute_r, con_key, tile, bufs, fbufs,
                        acc, facc, data):
            if si in bufs:
                keys = jnp.stack(
                    [tile["qid"]] + [tile["idx"][:, s] for s in dedup], axis=1)
                r, pop, bufs[si], s = _cer_compute(
                    keys, lambda: compute_r(tile, data), tile, bufs[si])
                acc = [a + v for a, v in zip(acc, s)]
            else:
                r, pop = compute_r(tile, data)
            raw_pop = pop
            r, pop, ok = self._finish(tile, r, pop, con_key, data)
            if si in fbufs:
                # qid-prefixed failure key: per-query con thresholds and
                # tables make the verdict a pure function of (qid, read-set)
                fkeys = jnp.stack(
                    [tile["qid"]] + [tile["idx"][:, s] for s in dedup],
                    axis=1)
                failed = tile["alive"] & ~ok
                wit = jnp.int32(2 * si) + (raw_pop > 0).astype(jnp.int32)
                fbufs[si], n_ins = _fail_insert(fkeys, failed, wit,
                                                fbufs[si])
                facc[2] = facc[2] + n_ins
            return r, pop, ok, acc

        def apply_fail_masks(k, cur, fbufs, facc):
            # post-expansion lookup-and-mask (rank stable; see
            # TileScheduler._build_step) — runs after the `active` mask so
            # deactivated-query rows neither hit nor count as misses
            if not fail_by_seg[k]:
                return
            alive0 = cur["alive"]
            dead = jnp.zeros_like(alive0)
            for (sj, slots) in fail_by_seg[k]:
                fkeys = jnp.stack(
                    [cur["qid"]] + [cur["idx"][:, s] for s in slots], axis=1)
                fhit = _fail_lookup(fkeys, alive0, fbufs[sj])
                facc[0] = facc[0] + fhit.sum().astype(jnp.int32)
                facc[1] = facc[1] + (alive0 & ~fhit).sum().astype(jnp.int32)
                dead = dead | fhit
            cur["alive"] = alive0 & ~dead
            facc[3] = facc[3] + dead.sum().astype(jnp.int32)

        def step(tile, r_in, cursor, bufs, fbufs, data, active, part=None):
            bufs = dict(bufs)
            fbufs = dict(fbufs)
            acc = [jnp.int32(0)] * 4                 # hits/misses/seen/ins
            facc = [jnp.int32(0)] * 4                # fail h/m/ins/pruned
            if root:
                r0, pop0 = root_compute_r(tile, data)
                r_in, _, _ = self._finish(tile, r0, pop0, root_con, data)
                if part is not None:
                    # per-query shard slice of the *pruned* root extension
                    # (thresholds apply to the global per-query popcount,
                    # never to one partition's — see TileScheduler)
                    r_in = r_in & part[tile["qid"]]
            frontiers = []
            alive_l, total_l = [], []
            proceed = None
            cur_tile, cur_r, cur_cursor = tile, r_in, cursor
            total_in = None
            for k, (expand, chain, leaf_i) in enumerate(built):
                cur, tot = expand(cur_tile, cur_r, cur_cursor, data)
                # drop rows of queries that already hit their limit. Applied
                # *after* expansion so bit ranks (and therefore host chunk
                # cursors into this frontier) are unaffected; counts of
                # deactivated queries freeze at >= limit and clamp.
                cur["alive"] = cur["alive"] & active[cur["qid"]]
                if k == 0:
                    total_in = tot.astype(jnp.int32)
                else:
                    cur["alive"] = cur["alive"] & proceed
                apply_fail_masks(k, cur, fbufs, facc)
                last = None
                for (sj, dedup, compute_r, con_key) in chain:
                    r, pop, ok, acc = run_compute(sj, dedup, compute_r,
                                                  con_key, cur, bufs, fbufs,
                                                  acc, facc, data)
                    last = (r, pop, ok)
                    if not leaf_i and sj == chain[-1][0]:
                        break                        # exit compute: no store
                    bm = dict(cur["bm"])
                    bm[self._stages[sj][1]] = r
                    cur = {"idx": cur["idx"], "qid": cur["qid"], "bm": bm,
                           "alive": ok}
                if leaf_i:
                    terms = leaf_terms(cur)
                    count_q, ovf_q = leaf_reduce(terms, cur["alive"],
                                                 cur["qid"])
                    leaf_alive = cur["alive"].sum().astype(jnp.int32)
                    packed = jnp.stack(
                        [total_in, leaf_alive, *alive_l, *total_l, *acc,
                         *facc])
                    return (cur, terms, count_q, ovf_q, packed, frontiers,
                            bufs, fbufs)
                r2, pop2, ok2 = last
                alive_k = ok2.sum().astype(jnp.int32)
                total_k = jnp.sum(pop2, dtype=jnp.int32)
                frontiers.append((cur, r2))
                alive_l.append(alive_k)
                total_l.append(total_k)
                ok_here = (total_k <= t) & (alive_k > 0)
                proceed = ok_here if proceed is None else (proceed & ok_here)
                cur_tile, cur_r, cur_cursor = cur, r2, jnp.int32(0)

        return (step, exit_bounds, sorted(set(seg_cer)), seg_fail,
                n_computes, gather_ops)

    def superstep(self, b: int):
        """Cached jitted wrapper of `build_step(b)`: one device dispatch
        advancing a mixed-query frontier chunk from boundary `b` down to the
        per-query leaf reduction. Fresh traces bump `compiled_supersteps`
        (surfaced as `VectorStats.bucket_recompiles`)."""
        key = ("ss", b)
        if key in self._jit:
            return self._jit[key]
        step, exit_bounds, seg_cer, seg_fail, n_computes, gather_ops = \
            self.build_step(b)
        entry = (jax.jit(step), exit_bounds, seg_cer, seg_fail, n_computes,
                 gather_ops)
        self._jit[key] = entry
        self.compiled_supersteps += 1
        return entry

    def merge_fn(self, b: int):
        """Sibling-frontier merge with the query-id lane carried through."""
        key = ("merge", b)
        if key in self._jit:
            return self._jit[key]
        t = self.t

        def merge(ta, ra, tb, rb):
            idx = jnp.concatenate([ta["idx"], tb["idx"]])
            qid = jnp.concatenate([ta["qid"], tb["qid"]])
            bm = {u: jnp.concatenate([ta["bm"][u], tb["bm"][u]])
                  for u in ta["bm"]}
            r = jnp.concatenate([ra, rb])
            live = bitops.row_popcount(r) > 0
            order = jnp.argsort(~live)[:t]           # stable: live first
            tile = {"idx": idx[order], "qid": qid[order],
                    "bm": {u: c[order] for u, c in bm.items()},
                    "alive": live[order]}
            return tile, r[order]

        fn = jax.jit(merge)
        self._jit[key] = fn
        return fn


# one BatchProgram per (signature, padded query count, traced knobs): shared
# by every SuperbatchScheduler whose bucket matches, across Matcher sessions.
# LRU-bounded — each program pins its jitted supersteps, and a long-running
# server sees an open-ended stream of padded shapes.
_PROGRAMS: "OrderedDict[tuple, BatchProgram]" = OrderedDict()
_PROGRAMS_MAX = 32


def _get_batch_program(sig, n_queries, *, use_cv, use_cer, use_fail):
    key = (sig, n_queries, use_cv, use_cer, use_fail)
    prog = _PROGRAMS.get(key)
    if prog is None:
        prog = BatchProgram(sig, n_queries, use_cv=use_cv, use_cer=use_cer,
                            use_fail=use_fail)
        _PROGRAMS[key] = prog
        while len(_PROGRAMS) > _PROGRAMS_MAX:
            _PROGRAMS.popitem(last=False)
    else:
        _PROGRAMS.move_to_end(key)
    return prog


class SuperbatchScheduler:
    """Cross-query superbatch runtime: one host work loop drains interleaved
    frontiers from every query in a shape-signature bucket through the shared
    BatchProgram supersteps. Per-query counts come back segment-summed from
    the leaf reduction; CER ring buffers are scheduler-lifetime and keyed by
    (query id, read-set), so a warm scheduler (Matcher caches them per
    bucket) reuses extensions across runs without ever crossing queries."""

    def __init__(self, plans, *, tile_rows: int = 256, use_cv: bool = True,
                 use_dedup: bool = True, use_cer_buffer: bool = True,
                 cer_buffer_slots: int = 256,
                 use_failure_cache: bool = True,
                 failure_cache_slots: int = 64, pack_tiles: bool = True,
                 overlap: bool = True):
        from .plan import _pow2ceil, plan_shape_signature
        if not plans:
            raise ValueError("superbatch needs at least one plan")
        sigs = {plan_shape_signature(p, tile_rows=tile_rows) for p in plans}
        if len(sigs) > 1:
            raise ValueError("superbatch plans must share one shape "
                             f"signature, got {len(sigs)}")
        self.sig = next(iter(sigs))
        self.plans = list(plans)
        self.nq = len(plans)
        self.nq_pad = _pow2ceil(self.nq)
        self.t = tile_rows
        self.pack_tiles = pack_tiles
        self.overlap = overlap
        self.program = _get_batch_program(
            self.sig, self.nq_pad, use_cv=use_cv,
            use_cer=(use_dedup and use_cer_buffer),
            use_fail=use_failure_cache)
        self.data = stack_batch_inputs(self.sig, self.plans, self.nq_pad)
        self._buffers = {
            si: _init_cer_buffer(cer_buffer_slots,
                                 1 + len(self.program.dedup_slots(si)),
                                 self.program.stage_width(si))
            for si in self.program._cer_stages}
        self._fail_buffers = {
            si: _init_fail_buffer(failure_cache_slots,
                                  1 + len(self.program.dedup_slots(si)))
            for si in self.program._fail_stages}
        # test hook: called with the scheduler after every superstep's
        # buffer fold-back (tests corrupt _fail_buffers mid-run through it)
        self.fail_debug_hook = None
        self.stats = VectorStats()

    def _push_frontier(self, b, tile, r, alive_n, total, stack, pending):
        st = self.stats
        if self.pack_tiles and alive_n * 2 <= self.t:
            pend = pending.get(b)
            if pend is None:
                pending[b] = [tile, r, alive_n, total]
            elif pend[2] + alive_n <= self.t:
                mtile, mr = self.program.merge_fn(b)(pend[0], pend[1], tile, r)
                st.device_steps += 1
                st.packed_tiles += 1
                pending[b] = [mtile, mr, pend[2] + alive_n, pend[3] + total]
            else:
                stack.append((b, pend[0], pend[1], 0, pend[3]))
                pending[b] = [tile, r, alive_n, total]
        else:
            stack.append((b, tile, r, 0, total))

    def run(self, *, limit: int = 1_000_000, max_steps: int | None = None):
        """Drain every query to completion (or `limit` embeddings each /
        `max_steps` total dispatches for the whole bucket). Returns
        (per-query counts, VectorStats, timed_out)."""
        prog = self.program
        st = self.stats = VectorStats()
        st.batched_queries = self.nq
        compiled_before = prog.compiled_supersteps
        t = self.t
        counts = [0] * self.nq
        timed_out = False
        singles = list(prog.leaf[0])
        groups = [list(g) for g in prog.leaf[1]]
        # queries that reached `limit` deactivate: their frontier rows are
        # masked dead inside subsequent supersteps (counts freeze and clamp)
        active_np = np.zeros(self.nq_pad, bool)
        active_np[:self.nq] = True
        active = jnp.asarray(active_np)

        root_tile = {"idx": jnp.zeros((self.nq_pad, 0), jnp.int32),
                     "qid": jnp.arange(self.nq_pad, dtype=jnp.int32),
                     "bm": {},
                     "alive": jnp.arange(self.nq_pad) < self.nq}
        root_r = jnp.zeros((self.nq_pad, prog.widths[0]), jnp.uint32)
        # (boundary, tile, R, cursor, total bits or -1 for the root item)
        stack: list = [(0, root_tile, root_r, 0, -1)]
        pending: dict[int, list] = {}

        def dispatch(item):
            """One batched superstep, no readback wait (see
            TileScheduler._dispatch_fused for the chaining argument)."""
            b, tile, r, cursor, tot = item
            fn, exit_bounds, seg_cer, seg_fail, n_computes, gather_ops = \
                prog.superstep(b)
            bufs = {si: self._buffers[si] for si in seg_cer}
            fbufs = {si: self._fail_buffers[si] for si in seg_fail}
            with jax.enable_x64(True):               # leaf reduce is int64
                (leaf_tile, terms, cnt_q, ovf_q, packed, frontiers, bufs2,
                 fbufs2) = fn(tile, r, jnp.int32(cursor), bufs, fbufs,
                              self.data, active)
            for si in seg_cer:
                self._buffers[si] = bufs2[si]
            for si in seg_fail:
                self._fail_buffers[si] = fbufs2[si]
            if self.fail_debug_hook is not None:
                self.fail_debug_hook(self)
            st.device_steps += 1
            st.supersteps += 1
            st.tiles += 1
            st.expansions += 1
            st.rows_processed += t * max(n_computes, 1)
            st.gather_and_ops += gather_ops
            if tot >= 0 and cursor + t < tot:
                stack.append((b, tile, r, cursor + t, tot))
            return {"item": item, "exit_bounds": exit_bounds,
                    "leaf_tile": leaf_tile, "terms": terms,
                    "frontiers": frontiers, "sync": (packed, cnt_q, ovf_q),
                    "np": None}

        def process(p):
            """Apply one synced readback; returns True when the ladder
            reached the leaf reduction (counts already folded)."""
            b, tile, r, cursor, tot = p["item"]
            packed_np, cnt_np, ovf_np = p["np"]
            exit_bounds = p["exit_bounds"]
            nb = len(exit_bounds)
            total_in = int(packed_np[0])
            leaf_alive = int(packed_np[1])
            alive_l = [int(v) for v in packed_np[2:2 + nb]]
            total_l = [int(v) for v in packed_np[2 + nb:2 + 2 * nb]]
            tail = [int(v) for v in packed_np[2 + 2 * nb:]]
            st.cer_hits += tail[0]
            st.cer_misses += tail[1]
            st.dedup_keys_seen += tail[2]
            st.dedup_unique += tail[3]
            st.fail_hits += tail[4]
            st.fail_misses += tail[5]
            st.fail_inserts += tail[6]
            st.fail_pruned_rows += tail[7]
            if tot < 0 and cursor + t < total_in:
                stack.append((b, tile, r, cursor + t, total_in))
            for k in range(nb):
                st.rows_alive += alive_l[k]
                if alive_l[k] == 0:
                    return False
                if total_l[k] <= t:
                    continue
                ft, fr = p["frontiers"][k]
                self._push_frontier(exit_bounds[k], ft, fr, alive_l[k],
                                    total_l[k], stack, pending)
                return False
            st.leaf_tiles += 1
            st.rows_alive += leaf_alive
            if bool(ovf_np.any()):
                # exact host fallback, per query (qid selects the rows)
                st.leaf_overflows += 1
                terms_np = np.asarray(p["terms"])
                alive_arr = np.asarray(p["leaf_tile"]["alive"])
                qid_np = np.asarray(p["leaf_tile"]["qid"])
                for qi in range(self.nq):
                    sel = qid_np == qi
                    counts[qi] += leaf_count_host(singles, groups,
                                                  terms_np[sel],
                                                  alive_arr[sel])
            else:
                for qi in range(self.nq):
                    counts[qi] += int(cnt_np[qi])
            return True

        while stack or pending:
            if not stack:
                b = max(pending)                     # flush deepest first
                tile_p, r_p, _, tot_p = pending.pop(b)
                stack.append((b, tile_p, r_p, 0, tot_p))
                continue
            if max_steps is not None and st.device_steps >= max_steps:
                timed_out = True
                break
            st.peak_stack = max(st.peak_stack, len(stack) + len(pending))
            # double-buffered claim of up to two items; the discipline is
            # shared by overlap on/off (see TileScheduler._run_fused)
            first = dispatch(stack.pop())
            if not self.overlap:
                _sync_inflight(st, [first])
            inflight = [first]
            if stack and (max_steps is None
                          or st.device_steps < max_steps):
                second = dispatch(stack.pop())
                if not self.overlap:
                    _sync_inflight(st, [second])
                inflight.append(second)
            if self.overlap:
                _sync_inflight(st, inflight)
            stop = False
            for p in inflight:
                process(p)
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
