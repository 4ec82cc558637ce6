"""Falsification-index kernels: matmul-form Eq. 4 + batched event replay.

The paper's clause index scores by iterating *false* literals and walking
their inclusion lists (Eq. 4). The list walk is pointer-chasing — exactly
what an accelerator hates — but the index carries a second, dense view of
the same information: the position matrix ``pos (m, n, 2o)`` is ``NA``
exactly where the clause excludes the literal (``indexing.validate`` pins
``(pos != NA) == include_mask``). The membership mask therefore *is* the
include mask, and Eq. 4 collapses to one contraction:

    falsified(b, i, j)  =  Σ_k false_lit(b, k) · member(i, j, k)  >  0
    votes(b, i)         =  -Σ_j falsified(b, i, j) · pol(j)

No per-sample vmap, no (m, 2o, cap) scatter-max — one MXU/GEMM-friendly
matmul over the literal axis plus a tiny vote reduction. Shard-locality is
free: ``pos`` tiles over the clause axis, partial votes add, and one (B, m)
psum completes the global scores (the ``indexed_votes`` partitioning
contract in ``kernels/backend.py``).

Two bodies live here:

  * :func:`indexed_votes_xla` — the XLA reference (float32 GEMM over 0/1
    operands; counts stay < 2²⁴ so the arithmetic is exact, and the result
    is bit-identical to the integer form).
  * :func:`indexed_votes` — the fused Pallas body: a clause tile's
    membership block meets the batch tile's false-literal block on the MXU,
    one literal tile at a time; the hit counts accumulate in VMEM scratch,
    the falsified bitmask never leaves VMEM, and votes accumulate into a
    lane-dense class block over the clause-tile grid axis (same tiling
    idiom as ``kernels/clause_eval.py``).

Maintenance is the third body: :func:`index_update_batched` replays a
fixed-shape masked event buffer in O(events) *vectorised* work instead of
``apply_events``'s fully serialised scan-of-cond (one XLA loop iteration
per buffer slot, thousands per train step). Events are netted per TA cell,
grouped per inclusion list by a segment-cumsum over two stable sorts of
the buffer (never over the full state), survivors of deleted entries are
compacted, and net inserts append — a handful of vectorised scatters per
buffer. The result is order-equivalent to sequential replay: identical
``counts`` (exact overflow accounting — every valid event moves its list
count by ±1, cancelling pairs net 0), identical membership (``pos != NA``),
and per-list identical *contents as sets* (intra-list order is the one
thing sequential swap-with-last replay and batched compaction may disagree
on, and nothing observes it: scoring reads membership only, ``validate``
checks the lists↔pos bijection, not slot order). There is no Pallas kernel
body for it — the work is scatter-bound, which Pallas TPU has no edge on —
so both registry routes run the same batched replay (the primitive exists
for routing uniformity and its clause-axis partitioning contract).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.clause_eval import (
    BATCH_TILE, CLAUSE_TILE, LANE, accumulate_class_votes, pad_to)

# Mirrors core.indexing.NA — kernels/ stays free of core/ imports; the
# sentinel is part of the ClauseIndex layout contract (tests pin equality).
NA = jnp.int32(-1)

# Literal columns per grid step. The (1, CLAUSE_TILE, LIT_TILE) int32 pos
# block is 1 MB (2 MB double-buffered) plus a 1 MB f32 membership temporary,
# whatever 2o is: wider literal axes (IMDb-like 2o = 10k-40k) take more
# grid steps, never more VMEM. 2o <= LIT_TILE runs as one full-width block.
LIT_TILE = 2048


# ---------------------------------------------------------------------------
# indexed_votes — matmul-form Eq. 4
# ---------------------------------------------------------------------------


def indexed_votes_xla(pos: jax.Array, lit: jax.Array,
                      pol: jax.Array) -> jax.Array:
    """(m, n, 2o) position matrix + (B, 2o) literals + (n,) ±1 polarity →
    (B, m) int32 partial vote sums (Eq. 4: ``-Σ_{j falsified} pol_j``).

    ``pos != NA`` is the membership/include mask, so falsification is one
    contraction of the false-literal indicators against it. The GEMM runs
    in float32 (0/1 operands; per-clause hit counts ≤ 2o < 2²⁴ are exact),
    the vote reduction in int32 — bit-identical to an all-integer einsum,
    and the clause-sharded partial sums add (one psum completes them).
    Padding clause rows are all-``NA`` (never falsified) *and* carry sign-0
    polarity, so they are doubly inert.
    """
    m, n, L = pos.shape
    member = (pos != NA).reshape(m * n, L)                # (m·n, 2o)
    false_lit = (lit == 0)                                # (B, 2o)
    hits = jnp.dot(false_lit.astype(jnp.float32),
                   member.astype(jnp.float32).T)          # (B, m·n)
    falsified = (hits > 0).reshape(-1, m, n)
    return -jnp.einsum("bmn,n->bm", falsified.astype(jnp.int32),
                       pol.astype(jnp.int32))


def _indexed_votes_kernel(pos_ref, fl_ref, pol_ref, o_ref, hits_ref):
    """Grid (B_tiles, m, n_tiles, l_tiles); the literal tile l is fastest.

    pos_ref:  (1, CLAUSE_TILE, Lt)    int32 — position block (NA = excluded)
    fl_ref:   (BATCH_TILE, Lt)        f32   — false-literal indicators
    pol_ref:  (1, CLAUSE_TILE)        int32 — ±1 clause polarity (0 = padding)
    o_ref:    (BATCH_TILE, m_pad)     int32 — votes, resident over (i, j, l)
    hits_ref: (BATCH_TILE, CLAUSE_TILE) f32 scratch — false members so far
    """
    i, j, l = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when((i == 0) & (j == 0) & (l == 0))
    def _init_votes():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(l == 0)
    def _init_hits():
        hits_ref[...] = jnp.zeros_like(hits_ref)

    # one MXU contraction per literal tile: 0/1 operands, f32 accumulation,
    # counts ≤ 2o < 2²⁴ — exact, so `hits > 0` matches the XLA body
    member = (pos_ref[0] != -1).astype(jnp.float32)     # (Ct, Lt); -1 == NA
    hits_ref[...] += jax.lax.dot_general(
        fl_ref[...], member, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)             # (Bt, Ct)

    @pl.when(l == pl.num_programs(3) - 1)
    def _vote():
        falsified = hits_ref[...] > 0
        sign = pol_ref[...]                             # (1, Ct)
        votes = jnp.sum(jnp.where(falsified, -sign, 0), axis=1, keepdims=True)
        accumulate_class_votes(o_ref, i, votes)


@functools.partial(jax.jit, static_argnames=("interpret",))
def indexed_votes(
    pos: jax.Array,   # (m, n, 2o) int32 position matrix (NA = excluded)
    lit: jax.Array,   # (B, 2o) literal truth values
    pol: jax.Array,   # (n,) int32 ±1 clause polarity
    *,
    interpret: bool = True,
) -> jax.Array:
    """Fused Pallas Eq.-4 falsification votes: (B, m) int32.

    Same contract as :func:`indexed_votes_xla`; ``pol`` is the ±1 sign per
    clause *row of this tensor* — the global polarity single-device, the
    shard's local slice under shard_map (partial sums completed by the one
    vote psum). ``pos`` is read in place, never padded: a partial trailing
    clause tile meets sign-0 polarity, and a partial trailing literal tile
    meets false-literal columns padded with 0 (not false), so whatever the
    out-of-range block entries hold contributes nothing.
    """
    m, n, L = pos.shape
    b = lit.shape[0]
    ct, lt = min(n, CLAUSE_TILE), min(L, LIT_TILE)

    fl = pad_to(pad_to((lit == 0).astype(jnp.float32), 1, lt), 0, BATCH_TILE)
    polp = pad_to(pol.astype(jnp.int32)[None, :], 1, ct)
    b_pad, m_pad = fl.shape[0], pl.cdiv(m, LANE) * LANE

    grid = (b_pad // BATCH_TILE, m, pl.cdiv(n, ct), pl.cdiv(L, lt))
    out = pl.pallas_call(
        _indexed_votes_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, ct, lt), lambda bb, i, j, l: (i, j, l)),
            pl.BlockSpec((BATCH_TILE, lt), lambda bb, i, j, l: (bb, l)),
            pl.BlockSpec((1, ct), lambda bb, i, j, l: (0, j)),
        ],
        out_specs=pl.BlockSpec((BATCH_TILE, m_pad),
                               lambda bb, i, j, l: (bb, 0)),
        out_shape=jax.ShapeDtypeStruct((b_pad, m_pad), jnp.int32),
        scratch_shapes=[pltpu.VMEM((BATCH_TILE, ct), jnp.float32)],
        interpret=interpret,
    )(pos.astype(jnp.int32), fl, polp)
    return out[:b, :m]


# ---------------------------------------------------------------------------
# index_update — batched event replay (O(events), vectorised)
# ---------------------------------------------------------------------------


def _segment_layout(keys: jax.Array):
    """Stable-sort segment helpers for a (E,) int32 key vector.

    Returns ``(order, sorted_keys, start, last, first_idx)`` where ``order``
    is the stable sort permutation (equal keys keep buffer order), ``start``
    / ``last`` flag segment boundaries in sorted order, and ``first_idx[e]``
    is the sorted position of e's segment head (the cummax trick — no
    segment ids materialised, no data-sized temporaries).
    """
    order = jnp.argsort(keys)                             # stable
    sk = keys[order]
    idx = jnp.arange(keys.shape[0], dtype=jnp.int32)
    start = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]])
    last = jnp.concatenate([sk[:-1] != sk[1:], jnp.ones((1,), bool)])
    first_idx = jax.lax.associative_scan(
        jnp.maximum, jnp.where(start, idx, 0))
    return order, sk, start, last, first_idx


def index_update_batched(
    lists: jax.Array,      # (m, 2o, cap) int32 clause ids; NA beyond counts
    counts: jax.Array,     # (m, 2o) int32
    pos: jax.Array,        # (m, n, 2o) int32; NA where excluded
    cls: jax.Array,        # (E,) int32 event class
    clause: jax.Array,     # (E,) int32 event clause
    literal: jax.Array,    # (E,) int32 event literal
    is_insert: jax.Array,  # (E,) bool
    valid: jax.Array,      # (E,) bool — fixed-shape buffer mask
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Replay a masked event buffer in one vectorised pass (no scan).

    Precondition (the ``apply_events`` contract): valid events are genuine
    include-boundary crossings in buffer order — an insert lands on a cell
    that is currently excluded, a delete on one currently included (so
    repeated events on one cell strictly alternate). Under it the batched
    result is order-equivalent to sequential replay: identical ``counts``
    (±1 per valid event; insert/delete pairs on a cell cancel exactly, so
    overflow accounting matches to the unit), identical membership
    (``pos != NA``), per-list identical contents as sets with a consistent
    lists↔pos bijection. Only intra-list slot *order* may differ (batched
    compaction preserves relative order and appends net inserts in buffer
    order; sequential swap-with-last may permute) — unobservable to
    scoring, ``validate``, and work accounting. Capacity overflow drops the
    overflowing ids (``mode='drop'``) while counts keep the exact
    sequential value — the config error stays observable via ``validate``.

    ``interpret`` is accepted for kernel-backend routing uniformity and
    ignored: the replay is scatter-bound, so both registry routes run this
    same body (see the module docstring).
    """
    del interpret
    m, L, cap = lists.shape
    n = pos.shape[1]
    E = cls.shape[0]
    idx = jnp.arange(E, dtype=jnp.int32)
    v = valid.astype(bool)
    ins = is_insert.astype(bool)

    # -- net events per TA cell: alternation means an even run is a no-op
    # and an odd run's last event carries the whole run's effect
    cell = (cls * n + clause) * L + literal
    cell_big = jnp.int32(m * n * L)                        # invalid → own tail
    order, _, _, last, first_idx = _segment_layout(
        jnp.where(v, cell, cell_big))
    occ = idx - first_idx                                  # rank within run
    net_sorted = v[order] & last & (occ % 2 == 0)          # odd run length
    effective = jnp.zeros((E,), bool).at[order].set(net_sorted)
    eff_ins = effective & ins
    eff_del = effective & ~ins

    # -- per-list aggregates (dense (m, 2o) temporaries — tiny)
    n_del = jnp.zeros((m, L), jnp.int32).at[
        jnp.where(eff_del, cls, m), literal].add(1, mode="drop")
    n_ins = jnp.zeros((m, L), jnp.int32).at[
        jnp.where(eff_ins, cls, m), literal].add(1, mode="drop")
    new_counts = counts + n_ins - n_del

    # -- membership: net deletes leave the index now; inserts land after
    # their append slots are known
    pos2 = pos.at[jnp.where(eff_del, cls, m), clause, literal].set(
        NA, mode="drop")

    # -- group effective events per inclusion list (c, k): the segment head
    # is the list's representative (rebuilds the row once), and each net
    # insert's rank among its list's inserts fixes its append slot
    glist = cls * L + literal
    glist_big = jnp.int32(m * L)
    order2, _, start2, _, first_idx2 = _segment_layout(
        jnp.where(effective, glist, glist_big))
    rep_sorted = start2 & effective[order2]
    ins_ind = eff_ins[order2].astype(jnp.int32)
    pre = jnp.cumsum(ins_ind) - ins_ind                    # inserts before me
    rank_sorted = pre - pre[first_idx2]                    # …within my list
    rep = jnp.zeros((E,), bool).at[order2].set(rep_sorted)
    ins_rank = jnp.zeros((E,), jnp.int32).at[order2].set(rank_sorted)

    # -- compact survivors of every touched list (one row per representative)
    rows = lists[cls, literal]                             # (E, cap)
    old_cnt = counts[cls, literal]                         # (E,)
    slot = jnp.arange(cap, dtype=jnp.int32)[None, :]
    safe_ids = jnp.where(rows >= 0, rows, 0)
    still = pos2[cls[:, None], safe_ids, literal[:, None]] != NA
    surv = (slot < old_cnt[:, None]) & (rows >= 0) & still # (E, cap)
    new_slot = jnp.cumsum(surv.astype(jnp.int32), axis=1) - 1
    new_rows = jnp.full((E, cap), NA, jnp.int32).at[
        idx[:, None], jnp.where(surv, new_slot, cap)].set(
        jnp.where(surv, rows, NA), mode="drop")

    # -- scatter everything back: representative rows, survivor positions,
    # then net-insert appends (scatters touch disjoint cells by netting)
    rep_c = jnp.where(rep, cls, m)                         # OOB → drop
    new_lists = lists.at[rep_c, literal].set(new_rows, mode="drop")
    wc = jnp.where(surv & rep[:, None], cls[:, None], m)
    pos3 = pos2.at[
        wc, safe_ids, jnp.broadcast_to(literal[:, None], (E, cap))].set(
        new_slot, mode="drop")

    base = old_cnt - n_del[cls, literal]                   # survivors per list
    app_slot = base + ins_rank
    ins_c = jnp.where(eff_ins, cls, m)
    new_lists = new_lists.at[ins_c, literal, app_slot].set(
        clause, mode="drop")
    pos3 = pos3.at[ins_c, clause, literal].set(app_slot, mode="drop")
    return new_lists, new_counts, pos3
