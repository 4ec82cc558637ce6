"""Core TM configuration and state containers.

Layout conventions (paper §2-§3):
  * ``o``        — number of input features; literal k < o is x_k, literal
                   k >= o is ¬x_{k-o}; total ``2o`` literals.
  * ``ta_state`` — int16 tensor ``(m, n, 2o)`` of Tsetlin Automaton states in
                   ``[1, 2N]``; action = include iff state > N.
  * clause polarity — clauses ``[0, n/2)`` are positive, ``[n/2, n)`` negative
                   (paper Eq. 2/3).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class TMConfig:
    """Hyper-parameters of a (multiclass) Tsetlin Machine."""

    n_classes: int
    n_clauses: int          # clauses per class (half positive / half negative)
    n_features: int         # o
    n_states: int = 127     # N; state space is [1, 2N]
    s: float = 3.9          # specificity (reward/penalty split)
    threshold: int = 15     # T (vote clamp / annealing parameter)
    boost_true_positive: bool = False
    # Paper Eq. (4) counts never-falsified (incl. empty) clauses as true.
    # Classic TM inference outputs 0 for empty clauses. 1 == paper semantics.
    empty_clause_output: int = 1
    state_dtype: jnp.dtype = jnp.int16
    # Static engine-cache capacities (jit shapes must not depend on data):
    #   index_capacity  — per-literal inclusion-list rows (ClauseIndex);
    #                     None → worst case n_clauses.
    #   clause_capacity — per-clause included-literal rows ℓ_max
    #                     (CompactClauses); None → worst case 2o.
    # Tighter values trade memory/work for an overflow risk surfaced by
    # ``indexing.validate`` / ``indexing.validate_compact`` (cf. MoE expert
    # capacity factors).
    index_capacity: int | None = None
    clause_capacity: int | None = None
    # Kernel backend the TM primitives (clause_votes / clause_outputs /
    # ta_update) resolve through kernels/backend.py: 'auto' picks Pallas on
    # TPU and the XLA reference bodies elsewhere; 'pallas_interpret' runs the
    # kernel bodies through the Pallas interpreter (CI / debugging). Purely
    # an execution detail — results are bit-exact across backends, and the
    # checkpoint fingerprint ignores it.
    backend: str = "auto"

    def __post_init__(self):
        if self.n_clauses % 2:
            raise ValueError("n_clauses must be even (half per polarity)")
        from repro.kernels.backend import BACKENDS  # kernels/ is core-free
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown kernel backend {self.backend!r}; one of {BACKENDS}")
        if self.empty_clause_output not in (0, 1):
            raise ValueError("empty_clause_output must be 0 or 1")
        if self.index_capacity is not None and self.index_capacity < 1:
            raise ValueError("index_capacity must be >= 1")
        if self.clause_capacity is not None and self.clause_capacity < 1:
            raise ValueError("clause_capacity must be >= 1")

    @property
    def n_literals(self) -> int:
        """2o — total literal count (positive + negated features)."""
        return 2 * self.n_features

    @property
    def half_clauses(self) -> int:
        """n/2 — clauses per polarity."""
        return self.n_clauses // 2

    @property
    def resolved_index_capacity(self) -> int:
        """Inclusion-list capacity (``index_capacity`` or the worst case)."""
        return self.index_capacity if self.index_capacity is not None else self.n_clauses

    @property
    def resolved_clause_capacity(self) -> int:
        """Per-clause literal capacity (``clause_capacity`` or worst case)."""
        return (self.clause_capacity if self.clause_capacity is not None
                else self.n_literals)


class TMState(NamedTuple):
    """Learnable state of a TM (a pytree; checkpointable/shardable)."""

    ta_state: jax.Array  # (m, n, 2o) int16 in [1, 2N]

    @property
    def n_classes(self) -> int:
        """m — classes (leading ``ta_state`` axis)."""
        return self.ta_state.shape[0]

    @property
    def n_clauses(self) -> int:
        """n — clause rows (possibly padded, see DESIGN.md §9)."""
        return self.ta_state.shape[1]

    @property
    def n_literals(self) -> int:
        """2o — literals (trailing ``ta_state`` axis)."""
        return self.ta_state.shape[2]


def init_tm(cfg: TMConfig, rng: jax.Array | None = None) -> TMState:
    """All TAs start just on the *exclude* side of the boundary (state N).

    This is the standard initialisation and the one the paper's index
    construction relies on: with every TA excluding, all inclusion lists
    start empty.
    """
    del rng  # deterministic init; rng kept for API symmetry
    ta = jnp.full(
        (cfg.n_classes, cfg.n_clauses, cfg.n_literals),
        cfg.n_states,
        dtype=cfg.state_dtype,
    )
    return TMState(ta_state=ta)


def literals_from_input(x: jax.Array) -> jax.Array:
    """(…, o) {0,1} input → (…, 2o) literal truth values [x, ¬x]."""
    x = x.astype(jnp.uint8)
    return jnp.concatenate([x, 1 - x], axis=-1)


def include_mask(cfg: TMConfig, state: TMState) -> jax.Array:
    """(m, n, 2o) bool — TA action is *include*."""
    return state.ta_state > cfg.n_states


def clause_polarity(cfg: TMConfig) -> jax.Array:
    """(n,) int32 — +1 for positive clauses, -1 for negative."""
    return jnp.where(
        jnp.arange(cfg.n_clauses) < cfg.half_clauses, 1, -1
    ).astype(jnp.int32)
