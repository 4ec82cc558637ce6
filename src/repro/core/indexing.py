"""Clause indexing (paper §3) — the paper's contribution, TPU-native.

Three structures, all fixed-shape functional pytrees:

  * ``ClauseIndex`` — the paper's inclusion lists ``L[i,k]`` (capacity-bounded
    rows of clause ids) + counts ``n[i,k]`` + position matrix ``M[i,j,k]``.
    ``insert``/``delete`` are the paper's O(1) swap-with-last updates as O(1)
    functional scatters.
  * ``indexed_scores`` — the paper's inference (Eq. 4): a sample's false
    literals falsify exactly the clauses in their inclusion lists. The hot
    body is the *matmul form*: ``pos != NA`` is the membership/include mask
    (``validate`` pins the identity), so the falsified-union is one
    contraction of false-literal indicators against it — no list walk, no
    scatter (``kernels/indexed.py``; routed per ``TMConfig.backend`` through
    the ``indexed_votes`` registry primitive).
  * ``index_update`` — batched O(events) replay of a masked event buffer
    (the ``index_update`` primitive): net events per TA cell, group per
    inclusion list via segment-cumsum, one vectorised scatter per buffer.
    Order-equivalent to the sequential ``apply_events`` oracle (kept, and
    pinned equivalent by property tests) with exact overflow accounting.
  * ``compact`` / ``compact_eval`` — the transpose (clause → included-literal
    indices), the gather-friendly layout a TPU prefers; work ∝ n·ℓ_max
    instead of n·2o, exploiting the *same* sparsity as the paper's lists
    (Σ clause lengths == Σ list lengths).

Capacity is the analogue of MoE expert capacity: lists are padded to
``capacity`` entries; overflow is a config error surfaced by ``validate``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.types import TMConfig, TMState, include_mask, literals_from_input

NA = jnp.int32(-1)


class ClauseIndex(NamedTuple):
    lists: jax.Array   # (m, 2o, cap) int32 clause ids; NA beyond counts
    counts: jax.Array  # (m, 2o) int32
    pos: jax.Array     # (m, n, 2o) int32 position of clause j in list k; NA if absent

    @property
    def capacity(self) -> int:
        return self.lists.shape[-1]


# ---------------------------------------------------------------------------
# Construction
# ---------------------------------------------------------------------------


def shard_capacity(capacity: int, n_shards: int) -> int:
    """Per-shard list capacity for a clause-sharded index: ⌈capacity/S⌉.

    Capacity rows split with the clauses they hold: the per-shard worst case
    is the shard's clause count, which is ⌈n_clauses/S⌉ under the ragged
    clause geometry (DESIGN.md §9) — and the default capacity *is*
    ``n_clauses``, so the ceiling keeps every shard's worst case covered for
    any shard count, divisible or not. The assembled global
    ``(m, 2o, S·⌈capacity/S⌉)`` lists tensor is opaque storage outside
    shard_map; shard-local lists hold *local* clause ids, which stay dense
    (``[0, n_local)``) under clause-axis padding because padding rows never
    include a literal and therefore never enter a list.
    """
    return -(-capacity // n_shards)


def empty_index(cfg: TMConfig, capacity: int) -> ClauseIndex:
    """All TAs exclude ⇒ all lists empty (paper: 'rather straightforward')."""
    m, n, L = cfg.n_classes, cfg.n_clauses, cfg.n_literals
    return ClauseIndex(
        lists=jnp.full((m, L, capacity), NA, jnp.int32),
        counts=jnp.zeros((m, L), jnp.int32),
        pos=jnp.full((m, n, L), NA, jnp.int32),
    )


def build_index(cfg: TMConfig, state: TMState, capacity: int) -> ClauseIndex:
    """Vectorised full (re)build from the include mask.

    Clause ids are placed in ascending order per list. Equivalent to
    replaying inserts in clause order (tests pin this equivalence).
    """
    inc = include_mask(cfg, state)                      # (m, n, 2o)
    inc_t = jnp.swapaxes(inc, 1, 2)                     # (m, 2o, n)
    counts = inc_t.sum(-1).astype(jnp.int32)            # (m, 2o)
    # slot of clause j within list (i,k): number of including clauses < j
    slot = jnp.cumsum(inc_t.astype(jnp.int32), axis=-1) - 1  # (m, 2o, n)
    slot = jnp.where(inc_t, slot, NA)
    m, L, n = inc_t.shape
    cap = capacity
    # scatter clause ids into lists
    lists = jnp.full((m, L, cap), NA, jnp.int32)
    clause_ids = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (m, L, n))
    safe_slot = jnp.where(slot >= 0, slot, cap)          # out-of-range drops
    lists = lists.at[
        jnp.arange(m)[:, None, None],
        jnp.arange(L)[None, :, None],
        safe_slot,
    ].set(jnp.where(inc_t, clause_ids, NA), mode="drop")
    pos = jnp.swapaxes(slot, 1, 2)                       # (m, n, 2o)
    return ClauseIndex(lists=lists, counts=counts, pos=pos)


def validate(cfg: TMConfig, state: TMState, index: ClauseIndex) -> dict:
    """Invariant checks (used by property tests): returns bool scalars."""
    inc = include_mask(cfg, state)
    rebuilt_counts = jnp.swapaxes(inc, 1, 2).sum(-1).astype(jnp.int32)
    counts_ok = jnp.all(index.counts == rebuilt_counts)
    overflow_ok = jnp.all(index.counts <= index.capacity)
    # membership: pos[i,j,k] != NA  ⇔  include[i,j,k]
    member_ok = jnp.all((index.pos != NA) == inc)
    # round-trip: lists[i, k, pos[i,j,k]] == j wherever included
    m, n, L = index.pos.shape
    ii = jnp.arange(m)[:, None, None]
    kk = jnp.arange(L)[None, None, :]
    safe_pos = jnp.where(index.pos != NA, index.pos, 0)
    back = index.lists[ii, kk, safe_pos]                 # (m, n, 2o)
    jj = jnp.arange(n, dtype=jnp.int32)[None, :, None]
    roundtrip_ok = jnp.all(jnp.where(index.pos != NA, back == jj, True))
    return dict(
        counts_ok=counts_ok,
        overflow_ok=overflow_ok,
        member_ok=member_ok,
        roundtrip_ok=roundtrip_ok,
    )


# ---------------------------------------------------------------------------
# O(1) maintenance (paper §3 "Index Construction and Maintenance")
# ---------------------------------------------------------------------------


def insert(index: ClauseIndex, i: jax.Array, j: jax.Array, k: jax.Array) -> ClauseIndex:
    """TA (i, j, k) flipped exclude→include: append j to list (i, k).

        n_k^i       ← n_k^i + 1
        L_k^i[n]    ← j
        M_k^{ij}    ← n
    (0-based here; the paper writes 1-based.) O(1) scatters.
    """
    c = index.counts[i, k]
    lists = index.lists.at[i, k, c].set(j.astype(jnp.int32), mode="drop")
    pos = index.pos.at[i, j, k].set(c)
    counts = index.counts.at[i, k].add(1)
    return ClauseIndex(lists=lists, counts=counts, pos=pos)


def delete(index: ClauseIndex, i: jax.Array, j: jax.Array, k: jax.Array) -> ClauseIndex:
    """TA (i, j, k) flipped include→exclude: swap-with-last removal.

        p                 ← M_k^{ij}
        L_k^i[p]          ← L_k^i[n-1]      (overwrite with last)
        M_k^{i, moved}    ← p
        n_k^i             ← n_k^i - 1
        M_k^{ij}          ← NA
    O(1) scatters; bit-for-bit the paper's pointer algebra.
    """
    p = index.pos[i, j, k]
    last = index.counts[i, k] - 1
    moved = index.lists[i, k, last]
    lists = index.lists.at[i, k, p].set(moved)
    pos = index.pos.at[i, moved, k].set(p)
    lists = lists.at[i, k, last].set(NA)
    counts = index.counts.at[i, k].add(-1)
    pos = pos.at[i, j, k].set(NA)
    return ClauseIndex(lists=lists, counts=counts, pos=pos)


class Event(NamedTuple):
    """A TA include/exclude boundary crossing."""

    cls: jax.Array     # ()
    clause: jax.Array  # ()
    literal: jax.Array # ()
    is_insert: jax.Array  # () bool
    valid: jax.Array   # () bool — masking for fixed-shape event buffers


def apply_events(index: ClauseIndex, events: Event) -> ClauseIndex:
    """Replay a fixed-shape, masked event buffer; each event is O(1).

    The *sequential oracle*: one ``lax.scan`` iteration per buffer slot,
    exactly the paper's one-event-at-a-time pointer algebra. The production
    path is :func:`index_update` (batched replay, no scan) — property tests
    pin the two equivalent on membership, counts (incl. overflow) and the
    lists↔pos bijection; this body stays as the semantics reference.
    """

    def body(idx, ev):
        def do(idx):
            return jax.lax.cond(
                ev.is_insert,
                lambda ix: insert(ix, ev.cls, ev.clause, ev.literal),
                lambda ix: delete(ix, ev.cls, ev.clause, ev.literal),
                idx,
            )
        return jax.lax.cond(ev.valid, do, lambda ix: ix, idx), None

    out, _ = jax.lax.scan(body, index, events)
    return out


def index_update(index: ClauseIndex, events: Event,
                 backend: str = "auto") -> ClauseIndex:
    """Batched event replay — the production form of :func:`apply_events`.

    Routes the ``index_update`` registry primitive (``kernels/indexed.py``):
    the whole buffer lands in a handful of vectorised scatters instead of a
    serialised scan, order-equivalent to sequential replay (identical
    membership/counts/bijection; intra-list slot order is the one
    unobservable difference — see the kernel docstring's ordering argument).
    Shard-local under shard_map exactly like ``apply_events`` was: every
    operand spec in the primitive's partitioning contract mirrors the
    indexed engine's ``cache_pspec``.
    """
    from repro.kernels.backend import resolve  # lazy: kernels/ is core-free

    fn = resolve("index_update", backend)
    lists, counts, pos = fn(
        index.lists, index.counts, index.pos,
        events.cls, events.clause, events.literal,
        events.is_insert, events.valid)
    return ClauseIndex(lists=lists, counts=counts, pos=pos)


class EventBuffer(NamedTuple):
    """A fixed-capacity masked event buffer + its overflow counter.

    ``overflow`` counts the boundary crossings that did **not** fit in the
    buffer — dropped events leave every derived cache silently stale, so a
    non-zero counter is a config error (``max_events`` too small for the
    batch). The counter makes that failure observable for the cost of one
    scalar: callers assert ``overflow == 0`` after a step instead of sizing
    buffers to the ``n_classes·n_clauses·n_literals`` worst case up front
    (``TMBundle.event_overflow`` accumulates it across steps).
    """

    events: Event       # (max_events,) leaves
    overflow: jax.Array # () int32 — changed cells beyond capacity


def events_from_transition(
    old_include: jax.Array, new_include: jax.Array, max_events: int
) -> EventBuffer:
    """Diff two include masks into a fixed-capacity counted event buffer.

    Used by the learning loop to keep the index in sync after feedback:
    the TM updates states densely (TPU-friendly), then the index absorbs
    only the boundary crossings — exactly the events the paper's CPU
    implementation applies one by one.

    Selection is two cumsums + one scatter, not a sort: cell i's buffer
    slot is its rank among changed cells (changed) or ``total`` plus its
    rank among unchanged ones (padding), which reproduces the stable
    ``argsort(~changed)[:max_events]`` bit-for-bit — first ``max_events``
    changed cells in ascending cell order, then ascending unchanged fill —
    at O(cells) work instead of a full O(cells·log) sort every train step
    (regression-pinned in tests/test_tm_indexing.py).

    The buffer is built without gathers, from that one scatter and
    elementwise ops. ``valid`` needs no look-up: the slot map is a
    bijection that gives changed cells the slots ``0..total-1``, so slot
    s holds a changed cell exactly when ``s < total``. ``is_insert`` is a
    property of the cell, so it travels with the cell's index through the
    scatter as the ``uint32`` payload ``cell·2 + new_bit``. The ``int32``
    cell index addresses at most 2³¹ − 1 cells, so the payload stays below
    2³² and cannot overflow.
    """
    changed = old_include != new_include                 # (m, n, 2o)
    flat = changed.reshape(-1)
    m, n, L = old_include.shape
    total = jnp.sum(flat, dtype=jnp.int32)
    # a buffer longer than the cell count degenerates to "all cells",
    # matching the old ``order[:max_events]`` slice semantics
    max_events = min(max_events, flat.shape[0])
    ranks = jnp.cumsum(flat.astype(jnp.int32)) - 1       # rank among changed
    pad_ranks = total + jnp.cumsum((~flat).astype(jnp.int32)) - 1
    slot = jnp.where(flat, ranks, pad_ranks)             # bijection on cells
    payload = (jnp.arange(flat.shape[0], dtype=jnp.uint32) * 2
               + new_include.reshape(-1).astype(jnp.uint32))
    packed = jnp.zeros((max_events,), jnp.uint32).at[slot].set(
        payload, mode="drop")
    sel = (packed >> 1).astype(jnp.int32)
    is_insert = (packed & 1) == 1
    valid = jnp.arange(max_events, dtype=jnp.int32) < total
    cls, rem = jnp.divmod(sel, n * L)
    clause, literal = jnp.divmod(rem, L)
    return EventBuffer(
        events=Event(
            cls=cls.astype(jnp.int32),
            clause=clause.astype(jnp.int32),
            literal=literal.astype(jnp.int32),
            is_insert=is_insert,
            valid=valid,
        ),
        overflow=jnp.maximum(total - max_events, 0).astype(jnp.int32),
    )


# ---------------------------------------------------------------------------
# Index-based inference (paper §3 "Index Based Inference", Eq. 4)
# ---------------------------------------------------------------------------


def indexed_partial_scores(
    index: ClauseIndex, x: jax.Array, pol: jax.Array
) -> jax.Array:
    """(B, o) inputs + per-clause ±1 polarity → (B, m) partial vote sums.

    The shard-local form of Eq. 4: for each false literal k, the clauses in
    L[i,k] are falsified; the contribution is ``-Σ_{j falsified} pol_j``
    (= |C_F^-| - |C_F^+| over the clauses this index covers). With a
    *clause-sharded* index — every shard owns its own lists over its own
    clause ids — the falsified-union is shard-local and the partial sums add,
    so one psum over the clause axis reproduces the global Eq. 4 scores
    exactly (Σ pol = 0 over all clauses maps Eq. 3 votes onto Eq. 4).

    Body: the matmul form over the position matrix — ``pos != NA`` is the
    membership mask, so the falsified-union is one contraction (the
    ``indexed_votes`` XLA reference body; the engine resolves the same
    primitive per ``cfg.backend`` to run the fused Pallas kernel instead).
    The old per-sample vmap → (m, 2o, cap) scatter-max is gone.
    """
    from repro.kernels import indexed as kindexed  # lazy: mirror backend use

    return kindexed.indexed_votes_xla(index.pos, literals_from_input(x), pol)


def indexed_scores(cfg: TMConfig, index: ClauseIndex, x: jax.Array) -> jax.Array:
    """(B, o) inputs → (B, m) scores via falsification look-up.

    Scores are |C_F^-| - |C_F^+| (Eq. 4), which equals the vote sum of Eq. 3
    shifted by a per-class constant when empty clauses count as true —
    ``argmax`` is unchanged; tests pin exact equality of scores against the
    dense path with ``empty_clause_output=1``.
    """
    from repro.core.types import clause_polarity

    return indexed_partial_scores(index, x, clause_polarity(cfg))


def indexed_work(index: ClauseIndex, x: jax.Array) -> jax.Array:
    """The paper's work metric: Σ_{k false} |L[i,k]| summed over classes.

    Used by benchmarks to reproduce the 0.02 (MNIST) / 0.006 (IMDb)
    work-ratio claims (§3 'Remarks').
    """
    lit = literals_from_input(x)
    false_lit = (lit == 0).astype(jnp.int32)              # (B, 2o)
    return jnp.einsum("bk,mk->b", false_lit, index.counts)


def dense_work(cfg: TMConfig) -> int:
    """Work of exhaustive evaluation: m·n·2o literal inspections."""
    return cfg.n_classes * cfg.n_clauses * cfg.n_literals


# ---------------------------------------------------------------------------
# Clause-compact (transpose) layout — TPU gather evaluation
# ---------------------------------------------------------------------------


class CompactClauses(NamedTuple):
    lit_idx: jax.Array  # (m, n, l_max) int32 literal indices; NA padded
    lengths: jax.Array  # (m, n) int32


def compact(cfg: TMConfig, state: TMState, l_max: int) -> CompactClauses:
    """Include mask → per-clause included-literal index rows."""
    inc = include_mask(cfg, state)                        # (m, n, 2o)
    lengths = inc.sum(-1).astype(jnp.int32)
    slot = jnp.cumsum(inc.astype(jnp.int32), axis=-1) - 1
    m, n, L = inc.shape
    lit_ids = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (m, n, L))
    safe_slot = jnp.where(inc, slot, l_max)
    lit_idx = jnp.full((m, n, l_max), NA, jnp.int32)
    lit_idx = lit_idx.at[
        jnp.arange(m)[:, None, None],
        jnp.arange(n)[None, :, None],
        safe_slot,
    ].set(jnp.where(inc, lit_ids, NA), mode="drop")
    return CompactClauses(lit_idx=lit_idx, lengths=lengths)


def compact_eval(
    cfg: TMConfig, comp: CompactClauses, x: jax.Array
) -> jax.Array:
    """(B, o) → (B, m, n) clause outputs touching only included literals.

    Work: B·m·n·l_max gathers vs B·m·n·2o dense — the paper's ratio
    (avg clause length / 2o ≈ 58/1568 ≈ 0.037 on MNIST). Empty clauses
    evaluate true (paper Eq. 4 semantics).
    """
    lit = literals_from_input(x)                          # (B, 2o)
    safe = jnp.where(comp.lit_idx == NA, 0, comp.lit_idx) # (m, n, l_max)
    gathered = lit[:, safe]                               # (B, m, n, l_max)
    ok = (gathered == 1) | (comp.lit_idx == NA)[None]
    return jnp.all(ok, axis=-1).astype(jnp.uint8)


def compact_scores(cfg: TMConfig, comp: CompactClauses, x: jax.Array) -> jax.Array:
    from repro.core.tm import clause_votes

    return clause_votes(cfg, compact_eval(cfg, comp, x))


def compact_apply_events(comp: CompactClauses, events: Event) -> CompactClauses:
    """Replay include/exclude events on the clause-compact layout.

    The transpose of ``apply_events``: rows are *clauses* holding literal ids,
    so an insert appends the literal, a delete is the same swap-with-last the
    paper uses for its lists. Rows are sets — ``compact_eval`` is order-blind —
    so event replay and a fresh ``compact()`` build agree up to row order.

    Contract (the TMBundle sync contract, DESIGN.md): events must be diffed
    against exactly the state this cache was built from. Capacity overflow
    loses the overflowing literal (a config error, surfaced by
    ``validate_compact``) but never corrupts surviving entries: an insert
    past ``ℓ_max`` leaves ``lengths`` clamped, and a delete of a literal the
    row never absorbed is a no-op.
    """
    l_max = comp.lit_idx.shape[-1]

    def body(c, ev):
        def do_insert(c):
            slot = c.lengths[ev.cls, ev.clause]
            fits = slot < l_max
            lit_idx = c.lit_idx.at[ev.cls, ev.clause, slot].set(
                ev.literal.astype(jnp.int32), mode="drop")
            lengths = c.lengths.at[ev.cls, ev.clause].add(
                jnp.where(fits, 1, 0))
            return CompactClauses(lit_idx=lit_idx, lengths=lengths)

        def do_delete(c):
            row = c.lit_idx[ev.cls, ev.clause]            # (l_max,)
            hit = row == ev.literal.astype(jnp.int32)
            present = jnp.any(hit)
            p = jnp.argmax(hit)
            last = c.lengths[ev.cls, ev.clause] - 1
            moved = row[last]
            lit_idx = c.lit_idx.at[ev.cls, ev.clause, p].set(
                jnp.where(present, moved, row[p]))
            lit_idx = lit_idx.at[ev.cls, ev.clause, last].set(
                jnp.where(present, NA, moved))
            lengths = c.lengths.at[ev.cls, ev.clause].add(
                jnp.where(present, -1, 0))
            return CompactClauses(lit_idx=lit_idx, lengths=lengths)

        def do(c):
            return jax.lax.cond(ev.is_insert, do_insert, do_delete, c)

        return jax.lax.cond(ev.valid, do, lambda c: c, c), None

    out, _ = jax.lax.scan(body, comp, events)
    return out


def validate_compact(cfg: TMConfig, state: TMState,
                     comp: CompactClauses) -> dict:
    """Invariant checks for the clause-compact layout (cf. ``validate``).

    ``lengths_ok`` fails when capacity overflow has lost literals —
    ``lengths`` can only track true clause lengths while they fit ℓ_max.
    """
    inc = include_mask(cfg, state)                       # (m, n, 2o)
    true_lengths = inc.sum(-1).astype(jnp.int32)
    lengths_ok = jnp.all(comp.lengths == true_lengths)
    overflow_ok = jnp.all(comp.lengths <= comp.lit_idx.shape[-1])
    # membership: every non-NA entry is an included literal of its clause
    m, n, L = inc.shape
    safe = jnp.where(comp.lit_idx == NA, 0, comp.lit_idx)
    back = inc[jnp.arange(m)[:, None, None],
               jnp.arange(n)[None, :, None], safe]       # (m, n, l_max)
    member_ok = jnp.all(jnp.where(comp.lit_idx != NA, back, True))
    slot_valid = (jnp.arange(comp.lit_idx.shape[-1])[None, None, :]
                  < comp.lengths[..., None])
    padding_ok = jnp.all(jnp.where(slot_valid, True, comp.lit_idx == NA))
    return dict(lengths_ok=lengths_ok, overflow_ok=overflow_ok,
                member_ok=member_ok, padding_ok=padding_ok)
