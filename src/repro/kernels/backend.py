"""Kernel backend registry: one declaration per TM primitive, many bodies.

The engines (core/engines.py) and the learning round (core/tm.py) used to
hard-wire *which* implementation of each hot primitive they ran — the Pallas
``bitpack`` engine carried an ``interpret`` constructor flag, ``bitpack_xla``
duplicated it wholesale, and training always took the XLA body. This module
makes the choice declarative instead: every TM primitive is registered once
with

  * an **XLA reference implementation** (the bit-exact semantics oracle,
    always executable),
  * a **Pallas implementation** (the TPU kernel; ``interpret=`` runs its body
    through the Pallas interpreter on hostless CI),
  * a **clause-axis partitioning contract** — how the primitive's operands
    and result partition over the mesh ``model`` (clause) axis, and whether
    the result is a partial sum completed by one psum (the vote all-reduce).

Callers resolve ``backend='auto'|'xla'|'pallas'|'pallas_interpret'`` —
threaded from ``TMConfig.backend`` / ``Topology.backend`` through
``TMSession`` — into a concrete callable via :func:`resolve`. ``auto``
resolves to compiled Pallas on TPU and to XLA everywhere else, so the same
config runs the fused kernels on hardware that has them and the reference
bodies elsewhere; it never resolves to the interpreter. Interpret mode is
asked for by name only (``backend='pallas_interpret'``: the tests, ``tm_serve
--backend pallas_interpret``, the dryrun route checks, the benchmark sweep).

The partitioning contract is the *declared* form of how the sharded layer
wires each primitive: a clause shard calls the same resolved callable on
its local slice (local include words, local ±1 polarity), and
``vote_reduce`` records that exactly one (B, m) psum over the clause axis
completes the result — the Massively Parallel TM contract. The wiring
itself lives in ``core/distributed.py``/``core/engines.py``;
tests/test_kernel_backends.py pins the declarations equal to it (so the
contract cannot drift from the code), and ``launch/dryrun.py --tm``
asserts the lowered collective profile per backend.

Primitives registered at import: ``clause_votes``, ``clause_outputs``,
``clause_outputs_rows``, ``ta_update``, ``indexed_votes``,
``index_update``. ``clause_outputs`` reads packed include words (the
callers that hold them: ``kernels/ops.py``); ``clause_outputs_rows``
reads one class's int16 TA row (the learning round, which holds no
words). The two share only the falsification predicate.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import clause_eval, indexed, ta_update as ta_update_mod

BACKENDS = ("auto", "xla", "pallas", "pallas_interpret")

# Mesh axis name the clause dimension shards over — must match
# core/engines.py's CLAUSE_AXIS (duplicated here to keep kernels/ free of
# core/ imports; pinned equal by tests/test_kernel_backends.py).
CLAUSE_AXIS = "model"


@dataclasses.dataclass(frozen=True)
class ClausePartitioning:
    """Clause-axis contract of one primitive under shard_map.

    ``in_specs``/``out_spec`` — PartitionSpecs of the positional operands /
    result over ``CLAUSE_AXIS`` (batch axes intentionally unnamed: the specs
    describe only how the *clause* dimension tiles).
    ``vote_reduce`` — True when shard-local results are partial sums and one
    psum over ``CLAUSE_AXIS`` yields the global result (the single (B, m)
    vote all-reduce); False when the primitive is clause-elementwise and
    needs no collective at all.
    ``clause_padding`` — how the primitive stays correct when its clause
    rows carry *padding* (the ragged geometry of DESIGN.md §9 pads the
    clause axis to ``clause_shards·⌈n/clause_shards⌉`` rows, and sequential
    data×clause composition pads each shard's sub-slices again):

      * ``'zero_polarity'`` — a padding row's ±1 polarity operand is 0, so
        its contribution to the partial vote sum is identically zero
        whatever the row evaluates to. No masking needed inside the body.
      * ``'masked_active'``  — a padding row's ``active`` gate operand is
        False, so both feedback branches apply a zero delta and the row
        passes through bit-identically (the "zero update mask").
      * ``'caller_sliced'``  — the primitive computes padding rows like any
        other; the caller owns discarding them (reassembly slice / vote
        weighting downstream).

    The sharded wiring (``core/distributed.py``) realises exactly these
    conventions — zero-padded polarity, the ``clause_mask``-gated update,
    the reassembly slice — and tests/test_kernel_backends.py pins the
    declarations equal to it.
    """

    in_specs: tuple
    out_spec: object
    vote_reduce: bool = False
    clause_padding: str = "caller_sliced"


@dataclasses.dataclass(frozen=True)
class Primitive:
    """One TM primitive: two bodies + the clause-axis contract."""

    name: str
    xla: Callable
    pallas: Callable  # must accept an ``interpret=`` keyword
    partitioning: ClausePartitioning


_PRIMITIVES: dict[str, Primitive] = {}


def register_primitive(prim: Primitive) -> Primitive:
    """Add a primitive to the registry (idempotent per name)."""
    if not prim.name:
        raise ValueError("primitive must set a non-empty name")
    _PRIMITIVES[prim.name] = prim
    return prim


def get_primitive(name: str) -> Primitive:
    """Look up a registered primitive by name (KeyError lists what exists)."""
    try:
        return _PRIMITIVES[name]
    except KeyError:
        raise KeyError(
            f"unknown TM primitive {name!r}; registered: "
            f"{registered_primitives()}") from None


def registered_primitives() -> tuple[str, ...]:
    """Registered primitive names, registration order."""
    return tuple(_PRIMITIVES)


def resolve_backend(backend: str = "auto") -> str:
    """``backend`` string → concrete mode (never ``'auto'``).

    ``'auto'`` is ``'pallas'`` on TPU and ``'xla'`` elsewhere — never
    ``'pallas_interpret'``, which only an explicit request selects.
    """
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown kernel backend {backend!r}; one of {BACKENDS}")
    if backend != "auto":
        return backend
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def pallas_mode() -> str:
    """The kernel-forcing mode for this host: compiled on TPU, interpreted
    elsewhere. What ``kernels/ops.py`` wrappers (and kernel tests) default
    to — unlike ``auto``, never falls back to XLA."""
    return "pallas" if jax.default_backend() == "tpu" else "pallas_interpret"


def resolve(name: str, backend: str = "auto") -> Callable:
    """Primitive name + backend string → concrete callable.

    The Pallas body comes back with ``interpret`` already bound, so call
    sites are backend-agnostic: ``resolve('clause_votes', cfg.backend)(...)``.
    """
    prim = get_primitive(name)
    mode = resolve_backend(backend)
    if mode == "xla":
        return prim.xla
    return functools.partial(prim.pallas,
                             interpret=(mode == "pallas_interpret"))


# ---------------------------------------------------------------------------
# XLA reference bodies (bit-exact semantics of the kernels, pure jnp)
# ---------------------------------------------------------------------------


def _clause_votes_xla(include_packed: jax.Array, lit_packed: jax.Array,
                      pol: jax.Array) -> jax.Array:
    """(m, n, W) packed includes + (B, W) packed literals + (n,) ±1 polarity
    → (B, m) int32 polarity-signed vote sums (Eq. 3/4 semantics: a clause is
    true iff no included literal is violated; empty clauses count true)."""
    viol = include_packed[None] & (~lit_packed)[:, None, None]   # (B,m,n,W)
    out = ~jnp.any(viol != 0, axis=-1)                           # (B,m,n)
    return jnp.einsum("bmn,n->bm", out.astype(jnp.int32),
                      pol.astype(jnp.int32))


def _clause_outputs_xla(include_packed: jax.Array,
                        lit_packed: jax.Array) -> jax.Array:
    """(m, n, W) packed includes + (B, W) packed literals → (B, m, n) int8
    clause outputs (learning semantics: empty clauses → 1)."""
    viol = include_packed[None] & (~lit_packed)[:, None, None]
    return (~jnp.any(viol != 0, axis=-1)).astype(jnp.int8)


def _clause_outputs_rows_xla(ta_row: jax.Array, lit: jax.Array, *,
                             n_states: int) -> jax.Array:
    """(n, 2o) TA row + (2o,) literals → (n,) int8 clause outputs
    (learning semantics: empty clauses → 1), as a dense float-einsum
    count of the included literals that are false."""
    include = ta_row > n_states
    false_cnt = jnp.einsum(
        "k,nk->n", (1 - lit).astype(jnp.float32),
        include.astype(jnp.float32))
    return (false_cnt < 0.5).astype(jnp.int8)


def _ta_update_xla(
    ta_row: jax.Array,       # (n, 2o) int16
    lit: jax.Array,          # (2o,)
    clause_out: jax.Array,   # (n,)
    gets_type_i: jax.Array,  # (n,) bool
    active: jax.Array,       # (n,) bool
    uniforms: jax.Array,     # (n, 2o) float32
    *,
    n_states: int,
    s: float,
    boost_true_positive: bool = False,
) -> jax.Array:
    """Type I / Type II feedback application, (n, 2o) int16 → int16.

    The reference body the Pallas ``ta_update`` kernel is pinned against
    (kernels/ref.py holds the numpy twin used by the oracle tests).
    """
    include = ta_row > n_states
    inv_s = 1.0 / s
    p_reward = 1.0 if boost_true_positive else 1.0 - inv_s
    c1 = (clause_out == 1)[:, None]
    l1 = (lit == 1)[None, :]
    reward = c1 & l1 & (uniforms < p_reward)
    penalty = ((c1 & ~l1) | ~c1) & (uniforms < inv_s)
    d1 = reward.astype(jnp.int16) - penalty.astype(jnp.int16)
    d2 = (c1 & ~l1 & ~include).astype(jnp.int16)
    act = active.astype(bool)[:, None]
    t1 = gets_type_i.astype(bool)[:, None]
    delta = jnp.where(act & t1, d1, jnp.where(act & ~t1, d2, 0))
    return jnp.clip(ta_row + delta, 1, 2 * n_states).astype(jnp.int16)


# ---------------------------------------------------------------------------
# Registrations
# ---------------------------------------------------------------------------

# Fused eval + vote: shard-local partial sums, ONE psum completes them.
register_primitive(Primitive(
    name="clause_votes",
    xla=_clause_votes_xla,
    pallas=clause_eval.clause_votes_packed,
    partitioning=ClausePartitioning(
        in_specs=(P(None, CLAUSE_AXIS, None),   # include words (m, n, W)
                  P(None, None),                # packed literals (B, W)
                  P(CLAUSE_AXIS)),              # polarity (n,)
        out_spec=P(None, None),                 # (B, m) partial votes
        vote_reduce=True,
        clause_padding="zero_polarity",         # sign-0 rows are inert
    ),
))

# Raw clause outputs (training / diagnostics): clause axis tiles through.
register_primitive(Primitive(
    name="clause_outputs",
    xla=_clause_outputs_xla,
    pallas=clause_eval.clause_outputs_packed,
    partitioning=ClausePartitioning(
        in_specs=(P(None, CLAUSE_AXIS, None),
                  P(None, None)),
        out_spec=P(None, None, CLAUSE_AXIS),    # (B, m, n)
        vote_reduce=False,
        clause_padding="caller_sliced",         # outputs feed a 0-pol vote
    ),
))

# Clause outputs of one class's TA row (the learning round): the row tiles
# by clause, the literals replicate, and no collective is needed.
register_primitive(Primitive(
    name="clause_outputs_rows",
    xla=_clause_outputs_rows_xla,
    pallas=clause_eval.clause_outputs_rows,
    partitioning=ClausePartitioning(
        in_specs=(P(CLAUSE_AXIS, None),         # ta_row (n, 2o)
                  P(None)),                     # lit (2o,)
        out_spec=P(CLAUSE_AXIS),                # (n,)
        vote_reduce=False,
        clause_padding="caller_sliced",         # outputs feed a 0-pol vote
    ),
))

# Matmul-form Eq. 4 over the falsification index's membership mask
# (pos != NA): shard-local partial vote sums, ONE psum completes them —
# the same collective profile as clause_votes, just a different cache.
register_primitive(Primitive(
    name="indexed_votes",
    xla=indexed.indexed_votes_xla,
    pallas=indexed.indexed_votes,
    partitioning=ClausePartitioning(
        in_specs=(P(None, CLAUSE_AXIS, None),   # positions (m, n, 2o)
                  P(None, None),                # literals (B, 2o)
                  P(CLAUSE_AXIS)),              # polarity (n,)
        out_spec=P(None, None),                 # (B, m) partial votes
        vote_reduce=True,
        clause_padding="zero_polarity",         # sign-0 rows are inert
    ),
))

# Batched event replay: every buffer column replicates (each shard diffs
# its own include slice, so local buffers only name local clauses), the
# index buffers tile over the clause axis exactly like the engine's
# cache_pspec, and no collective is needed. Both routes are the same
# vectorised body — the replay is scatter-bound (see kernels/indexed.py).
register_primitive(Primitive(
    name="index_update",
    xla=indexed.index_update_batched,
    pallas=indexed.index_update_batched,
    partitioning=ClausePartitioning(
        in_specs=(P(None, None, CLAUSE_AXIS),   # lists (m, 2o, cap)
                  P(None, CLAUSE_AXIS),         # counts (m, 2o)
                  P(None, CLAUSE_AXIS, None),   # pos (m, n, 2o)
                  P(None),                      # cls (E,)
                  P(None),                      # clause (E,)
                  P(None),                      # literal (E,)
                  P(None),                      # is_insert (E,)
                  P(None)),                     # valid (E,)
        out_spec=(P(None, None, CLAUSE_AXIS),
                  P(None, CLAUSE_AXIS),
                  P(None, CLAUSE_AXIS, None)),
        vote_reduce=False,
        clause_padding="masked_active",         # invalid events no-op
    ),
))

# Feedback application: clause-elementwise, no collective.
register_primitive(Primitive(
    name="ta_update",
    xla=_ta_update_xla,
    pallas=ta_update_mod.ta_update,
    partitioning=ClausePartitioning(
        in_specs=(P(CLAUSE_AXIS, None),         # ta_row (n, 2o)
                  P(None),                      # lit (2o,)
                  P(CLAUSE_AXIS),               # clause_out
                  P(CLAUSE_AXIS),               # gets_type_i
                  P(CLAUSE_AXIS),               # active
                  P(CLAUSE_AXIS, None)),        # uniforms (n, 2o)
        out_spec=P(CLAUSE_AXIS, None),
        vote_reduce=False,
        clause_padding="masked_active",         # False gate ⇒ zero delta
    ),
))
