"""Tsetlin Machine forward pass and learning (paper §2).

Evaluation paths (all semantically identical; cross-validated in tests):
  * ``dense_clause_outputs``   — exhaustive evaluation, the paper's baseline.
  * packed words (kernels/backend.py) — dense over 32x packed words
    (VPU-friendly), XLA or Pallas body per ``cfg.backend``.
  * ``compact_eval`` (indexing.py) — gather over included literals only;
    work ∝ Σ clause lengths (the paper's sparsity).
  * ``indexed_scores`` (indexing.py) — the paper's falsification index.

Learning implements Type I / Type II feedback with explicit uniform draws
passed in, so the pure-numpy oracle in ``core/ref.py`` can be driven with the
*same* randomness and compared bit-exactly.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import scopes
from repro.core.types import (
    TMConfig,
    TMState,
    clause_polarity,
    include_mask,
    literals_from_input,
)

# ---------------------------------------------------------------------------
# Forward pass
# ---------------------------------------------------------------------------


def dense_clause_outputs(
    cfg: TMConfig, state: TMState, x: jax.Array, *, empty_output: int | None = None
) -> jax.Array:
    """Exhaustive clause evaluation. x: (B, o) {0,1} → (B, m, n) uint8.

    A clause is true iff no included literal is false:
      falsified(b, i, j) = ∃k: include[i,j,k] ∧ ¬literal[b,k].
    Implemented as an integer matmul (false-literal count per clause) so the
    dense baseline is itself vectorised — the paper's C baseline is a tight
    loop; an un-vectorised JAX loop would strawman it.
    """
    lit = literals_from_input(x)                      # (B, 2o)
    inc = include_mask(cfg, state)                    # (m, n, 2o)
    false_lit = (1 - lit).astype(jnp.float32)         # (B, 2o)
    # count of included-and-false literals per clause
    counts = jnp.einsum("bk,mnk->bmn", false_lit, inc.astype(jnp.float32))
    out = (counts < 0.5).astype(jnp.uint8)            # (B, m, n)
    empty_output = cfg.empty_clause_output if empty_output is None else empty_output
    if empty_output == 0:
        empty = ~jnp.any(inc, axis=-1)                # (m, n)
        out = out * (1 - empty.astype(jnp.uint8))[None]
    return out


def clause_votes(cfg: TMConfig, clause_out: jax.Array) -> jax.Array:
    """(B, m, n) clause outputs → (B, m) polarity-signed vote sums (Eq. 3)."""
    pol = clause_polarity(cfg)                        # (n,)
    return jnp.einsum("bmn,n->bm", clause_out.astype(jnp.int32), pol)


def scores(cfg: TMConfig, state: TMState, x: jax.Array) -> jax.Array:
    """(B, m) class scores via the dense path."""
    return clause_votes(cfg, dense_clause_outputs(cfg, state, x))


def predict(cfg: TMConfig, state: TMState, x: jax.Array) -> jax.Array:
    """(B,) argmax class (Eq. 3)."""
    return jnp.argmax(scores(cfg, state, x), axis=-1)


# The packed-word evaluation bodies (XLA reference + Pallas kernel) live in
# kernels/backend.py — the packed engine resolves them per cfg.backend, so
# this module carries only the dense baseline and the learning semantics.


# ---------------------------------------------------------------------------
# Learning: Type I / Type II feedback (paper §2, Granmo 2018 semantics)
# ---------------------------------------------------------------------------


class FeedbackRands(NamedTuple):
    """Uniform draws consumed by one class-round of feedback.

    Passing these explicitly makes the update a deterministic function, so
    the numpy oracle can replay identical randomness.
    """

    clause_gate: jax.Array  # (n,)      uniforms vs update probability p
    type_i: jax.Array       # (n, 2o)   uniforms vs 1/s and (s-1)/s


def draw_feedback_rands(cfg: TMConfig, rng: jax.Array,
                        clause_start: jax.Array | None = None,
                        n_rows: int | None = None) -> FeedbackRands:
    """Draw one class-round's uniforms from ``rng``.

    With ``clause_start`` None: the full ``(n_clauses,)`` gate and
    ``(n_clauses, 2o)`` Type I uniforms. Otherwise only clause rows
    ``[clause_start, clause_start + n_rows)`` of those same draws, bit for
    bit (``uniform_rows``): a clause shard draws its own rows and nothing
    else. Padding rows of a ragged slice (DESIGN.md §9) get other values
    of the stream; ``clause_mask`` freezes them.
    """
    k1, k2 = jax.random.split(rng)
    if clause_start is None:
        return FeedbackRands(
            clause_gate=jax.random.uniform(k1, (cfg.n_clauses,)),
            type_i=jax.random.uniform(k2, (cfg.n_clauses, cfg.n_literals)),
        )
    return FeedbackRands(
        clause_gate=uniform_rows(k1, clause_start, n_rows, 1)[:, 0],
        type_i=uniform_rows(k2, clause_start, n_rows, cfg.n_literals),
    )


def flat_counters(start: jax.Array, n_rows: int,
                  width: int) -> tuple[jax.Array, jax.Array]:
    """The 64-bit row-major flat index ``(start + r)·width + c`` of an
    ``(n_rows, width)`` block, as (high, low) uint32 words (no x64 needed).

    The row's product with ``width`` is formed from 16-bit halves, so it is
    exact for any row and width below 2³²; the column add carries into the
    high word."""
    row = (jnp.asarray(start).astype(jnp.uint32)
           + jnp.arange(n_rows, dtype=jnp.uint32))
    r_lo, r_hi = row & 0xFFFF, row >> 16
    w_lo, w_hi = jnp.uint32(width & 0xFFFF), jnp.uint32(width >> 16)
    p0, p1, p2 = r_lo * w_lo, r_lo * w_hi, r_hi * w_lo
    mid = (p0 >> 16) + (p1 & 0xFFFF) + (p2 & 0xFFFF)
    lo = (p0 & 0xFFFF) | (mid << 16)
    hi = r_hi * w_hi + (p1 >> 16) + (p2 >> 16) + (mid >> 16)
    flat_lo = lo[:, None] + jnp.arange(width, dtype=jnp.uint32)[None, :]
    flat_hi = hi[:, None] + (flat_lo < lo[:, None]).astype(jnp.uint32)
    return flat_hi, flat_lo


def uniform_rows(key: jax.Array, start: jax.Array, n_rows: int,
                 width: int) -> jax.Array:
    """Rows ``[start, start + n_rows)`` of ``jax.random.uniform(key,
    (N, width))`` for any ``N >= start + n_rows``, bit for bit, without
    drawing the other rows.

    Under JAX's partitionable threefry (``jax_threefry_partitionable``, on
    by default) element ``(i, j)`` of a draw is threefry-2x32 of the key and
    the flat index ``i·width + j``, and ``uniform`` keeps the top 23 bits as
    the mantissa of a float in [1, 2) less 1. This function builds those
    counters for its rows alone and converts them the same way.
    ``tests/test_tm_core.py`` pins the layout against ``jax.random.uniform``
    so that a JAX release that changes it fails there.
    """
    from jax.extend.random import threefry2x32_p

    if not jax.config.jax_threefry_partitionable:
        raise RuntimeError("uniform_rows reproduces the partitionable "
                           "threefry layout; jax_threefry_partitionable is off")
    k1, k2 = jax.random.key_data(key)
    hi, lo = flat_counters(start, n_rows, width)
    b1, b2 = threefry2x32_p.bind(k1, k2, hi, lo)
    mantissa = ((b1 ^ b2) >> 9) | jnp.uint32(0x3F800000)
    floats = jax.lax.bitcast_convert_type(mantissa, jnp.float32) - 1.0
    return jnp.maximum(0.0, floats)


def _class_round(
    cfg: TMConfig,
    ta_row: jax.Array,       # (n, 2o) — states of one class (or a clause shard)
    lit: jax.Array,          # (2o,)
    rands: FeedbackRands,
    positive_round: jax.Array,  # scalar bool — True: target-class round
    *,
    pol: jax.Array | None = None,   # (n,) ±1 — pass the local slice when sharded
    # mesh axes the votes psum over: the clause axis, or (batch axes + clause
    # axis) when the sequential path additionally splits clauses over the
    # data axes (hierarchical data×clause sharding)
    axis_name: str | tuple[str, ...] | None = None,
    clause_mask: jax.Array | None = None,  # (n,) bool — False rows frozen
) -> jax.Array:
    """One feedback round for one class; returns updated (n, 2o) states.

    Clause-sharded learning (core/distributed.py) calls this with the local
    ``ta_row``/``rands``/``pol`` slices and the mesh clause ``axis_name``: the
    per-class vote is the *only* cross-shard quantity (one psum — the vote
    all-reduce of the Massively Parallel TM architecture); Type I/II feedback
    is clause-local given that vote.

    ``clause_mask`` marks the rows that are *real* clauses: ragged shard
    slices (DESIGN.md §9) pad their clause axis, and a padding row must stay
    bit-identical through the round — it is excluded from the update gate
    (``active``), so both feedback bodies apply a zero delta. Its vote
    contribution is already zero by the sign-0 polarity padding convention,
    so the mask never touches the vote sum.

    Both halves of the round resolve through the kernel backend registry
    (``cfg.backend``): clause evaluation (``clause_outputs_rows``, (n,) int8,
    empty clauses → 1) and feedback application (``ta_update``). The XLA
    body of the evaluation is a dense float-einsum falsification count. On
    the Pallas backends this is the fused training round: the clause
    outputs are read straight from the int16 row, nothing packed, and piped
    into the ``ta_update`` kernel with only the scalar vote in between,
    bit-exact with the XLA bodies (tests/test_kernel_backends.py pins it in
    both learning modes).
    """
    from repro.kernels import backend as kbackend

    mode = kbackend.resolve_backend(cfg.backend)
    clause_out = kbackend.resolve("clause_outputs_rows", mode)(
        ta_row, lit, n_states=cfg.n_states)
    if pol is None:
        pol = clause_polarity(cfg)
    t = float(cfg.threshold)
    vote_sum = jnp.sum(clause_out.astype(jnp.int32) * pol)
    if axis_name is not None:
        with jax.named_scope(scopes.VOTES):
            vote_sum = jax.lax.psum(vote_sum, axis_name)
    votes = jnp.clip(vote_sum, -t, t)
    p = jnp.where(positive_round, (t - votes) / (2 * t), (t + votes) / (2 * t))
    active = rands.clause_gate < p                    # (n,)
    if clause_mask is not None:
        active = active & clause_mask                 # padding rows frozen

    pos_pol = pol > 0
    # target round: positive clauses→Type I, negative→Type II; swapped otherwise
    gets_type_i = jnp.where(positive_round, pos_pol, ~pos_pol)

    apply_feedback = kbackend.resolve("ta_update", mode)
    new_row = apply_feedback(
        ta_row.astype(jnp.int16), lit, clause_out, gets_type_i, active,
        rands.type_i, n_states=cfg.n_states, s=cfg.s,
        boost_true_positive=cfg.boost_true_positive)
    return new_row.astype(cfg.state_dtype)


def update_sample(
    cfg: TMConfig,
    state: TMState,
    x: jax.Array,        # (o,)
    y: jax.Array,        # () int
    rng: jax.Array,
    *,
    pol: jax.Array | None = None,
    axis_name: str | tuple[str, ...] | None = None,
    clause_start: jax.Array | None = None,
    clause_mask: jax.Array | None = None,
) -> TMState:
    """One online update (the paper's per-sample learning).

    Target class receives a positive round; one uniformly drawn *other*
    class receives a negative round (standard multiclass TM scheme).

    When ``state`` holds only a clause shard, pass the shard's polarity
    slice ``pol``, the mesh clause ``axis_name`` (vote psum) and the shard's
    global ``clause_start`` — each shard draws only its own rows of the
    same uniform stream (``uniform_rows``), so the sharded update is
    bit-exact with the single-device one. ``clause_mask`` (n,) freezes
    padding rows of a ragged slice (see ``_class_round``).
    """
    lit = literals_from_input(x)
    ta = state.ta_state
    with jax.named_scope(scopes.DRAWS):
        k_neg, k_a, k_b = jax.random.split(rng, 3)
        # sample negative class ≠ y
        neg = jax.random.randint(k_neg, (), 0, cfg.n_classes - 1)
        neg = jnp.where(neg >= y, neg + 1, neg)
        n_rows = None if clause_start is None else ta.shape[1]
        rands_a = draw_feedback_rands(cfg, k_a, clause_start, n_rows)
        rands_b = draw_feedback_rands(cfg, k_b, clause_start, n_rows)
    row_pos = _class_round(cfg, ta[y], lit, rands_a, jnp.asarray(True),
                           pol=pol, axis_name=axis_name,
                           clause_mask=clause_mask)
    ta = ta.at[y].set(row_pos)
    row_neg = _class_round(cfg, ta[neg], lit, rands_b, jnp.asarray(False),
                           pol=pol, axis_name=axis_name,
                           clause_mask=clause_mask)
    ta = ta.at[neg].set(row_neg)
    return TMState(ta_state=ta)


def update_batch_sequential(
    cfg: TMConfig, state: TMState, xs: jax.Array, ys: jax.Array,
    rng: jax.Array, *,
    pol: jax.Array | None = None,
    axis_name: str | tuple[str, ...] | None = None,
    clause_start: jax.Array | None = None,
    mask: jax.Array | None = None,
    clause_mask: jax.Array | None = None,
) -> TMState:
    """Faithful online learning over a batch: lax.scan of per-sample updates.

    Sharded mode (kwargs set): the *full* batch is scanned on every clause
    shard — online learning is sequential in samples by definition — with one
    vote psum per class round as the only collective.

    ``mask`` (B,) bool marks valid samples: masked-out rows consume their
    randomness (so padded and unpadded streams stay key-aligned) but apply no
    state update — the padding contract for fixed-shape trailing batches.
    ``clause_mask`` (n,) bool marks valid *clause rows*: the transpose
    contract for ragged shard slices (padding rows frozen, DESIGN.md §9).
    """
    keys = jax.random.split(rng, xs.shape[0])
    valid = jnp.ones(xs.shape[0], bool) if mask is None else mask

    def body(st, inp):
        x, y, k, m = inp
        new = update_sample(cfg, st, x, y, k, pol=pol, axis_name=axis_name,
                            clause_start=clause_start,
                            clause_mask=clause_mask)
        return TMState(ta_state=jnp.where(m, new.ta_state, st.ta_state)), None

    out, _ = jax.lax.scan(body, state, (xs, ys, keys, valid))
    return out


def update_batch_parallel(
    cfg: TMConfig, state: TMState, xs: jax.Array, ys: jax.Array,
    rng: jax.Array, *,
    pol: jax.Array | None = None,
    axis_name: str | tuple[str, ...] | None = None,
    clause_start: jax.Array | None = None,
    batch_axes: tuple[str, ...] = (),
    batch_start: jax.Array | None = None,
    batch_total: int | None = None,
    mask: jax.Array | None = None,
    clause_mask: jax.Array | None = None,
) -> TMState:
    """Beyond-paper: batch-parallel update (deltas computed vs the *same*
    pre-batch state, then summed). An approximation of online learning —
    documented in DESIGN.md; used for throughput-oriented training.

    Sharded mode additionally shards the *batch*: ``xs`` holds this data
    shard's slice of a ``batch_total``-sized global batch starting at
    ``batch_start``; per-sample keys are the global split sliced to match
    (bit-exact with the single-device split), and the summed deltas are
    psum'd over ``batch_axes`` before the clip. ``mask`` (B,) bool zeroes
    the deltas of padded samples (randomness still consumed per row);
    ``clause_mask`` (n,) bool zeroes the deltas of padded clause rows
    (ragged shard slices, DESIGN.md §9).
    """
    if batch_total is None:
        keys = jax.random.split(rng, xs.shape[0])
    else:
        # global key stream, local slice — identical keys per global sample
        kd = jax.random.key_data(jax.random.split(rng, batch_total))
        kd = jax.lax.dynamic_slice_in_dim(kd, batch_start, xs.shape[0], 0)
        keys = jax.random.wrap_key_data(kd)

    def one(x, y, k):
        new = update_sample(cfg, state, x, y, k, pol=pol, axis_name=axis_name,
                            clause_start=clause_start,
                            clause_mask=clause_mask)
        return (new.ta_state.astype(jnp.int32) - state.ta_state.astype(jnp.int32))

    deltas = jax.vmap(one)(xs, ys, keys)
    if mask is not None:
        deltas = jnp.where(mask[:, None, None, None], deltas, 0)
    deltas = deltas.sum(axis=0)
    if batch_axes:
        deltas = jax.lax.psum(deltas, batch_axes)
    ta = jnp.clip(
        state.ta_state.astype(jnp.int32) + deltas, 1, 2 * cfg.n_states
    ).astype(cfg.state_dtype)
    return TMState(ta_state=ta)


def accuracy(cfg: TMConfig, state: TMState, xs: jax.Array, ys: jax.Array) -> jax.Array:
    """Fraction of ``xs`` rows whose argmax vote equals ``ys``."""
    return jnp.mean((predict(cfg, state, xs) == ys).astype(jnp.float32))
