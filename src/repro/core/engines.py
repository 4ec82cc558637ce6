"""Evaluation-engine registry — one TM, many interchangeable eval strategies.

The paper's point is that a trained TM admits several semantically identical
evaluation strategies with very different work profiles (exhaustive vs the
falsification index, Gorji et al. 2020); the Massively Parallel TM line
(Abeyrathna et al. 2020) shows that decoupling clause *evaluation* from TA
*state storage* is what unlocks scaling. This module is that API boundary:

  * ``EvalEngine`` — ``prepare(cfg, state) -> cache`` builds the engine's
    pytree cache (packed include words, ``CompactClauses``, ``ClauseIndex``);
    ``scores(cfg, cache, x)`` evaluates from the cache alone;
    ``update_cache(cfg, cache, state, events)`` brings the cache to the
    post-update state inside the jitted step, never through the host:
    ``compact`` and ``indexed`` absorb the include/exclude boundary
    crossings incrementally, while the packed words are repacked from the
    new state (one O(cells) pass, no scatter or sort, exact even when the
    event buffer overflows). A bundle whose only cache is the packed words
    reads no event, so its event buffer is dead code that XLA removes.
  * ``register_engine`` / ``get_engine`` / ``registered_engines`` — the
    registry. ``dense``, ``bitpack``, ``bitpack_xla``, ``compact`` and
    ``indexed`` register at import; new engines (sharded, weighted, …)
    plug in without touching the estimator, the shim, the parity tests or
    the benchmarks — all of which iterate the registry. Kernel-vs-XLA
    *bodies* are no longer an engine property: the packed engine resolves
    its evaluation through the kernel backend registry
    (``kernels/backend.py``, selected by ``cfg.backend``), and
    ``bitpack_xla`` is just ``bitpack`` pinned to ``backend='xla'``.

Engines that derive the *same* cache share it via ``cache_key`` (``bitpack``
and ``bitpack_xla`` both read the packed include words), so a ``TMBundle``
stores and maintains each distinct cache once.

Every method is pure and jit-compatible: cache shapes are static functions
of ``TMConfig`` (``resolved_index_capacity`` / ``resolved_clause_capacity``),
never of the data — the seed's ``np.asarray(include_mask(...)).max()`` host
round-trip at inference time is gone.

Score semantics: all engines implement the paper's Eq. 4 convention (empty /
never-falsified clauses count as true). With ``cfg.empty_clause_output == 1``
(the default) every engine returns *identical* scores; with 0 only ``dense``
follows the classic convention and the others still agree on ``argmax`` in
the usual case (tests pin the score identity in paper mode).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import indexing, tm
from repro.core.bitpack import pack_bits, packed_literals
from repro.core.indexing import Event
from repro.core.types import (
    TMConfig, TMState, clause_polarity, include_mask, literals_from_input)
from repro.kernels import backend as kbackend

# Mesh axis name the clause dimension shards over (production meshes call
# their tensor axis "model"; clauses are the TM's model dimension).
CLAUSE_AXIS = "model"


class EvalEngine:
    """Base class for evaluation engines. Subclass + ``register_engine``.

    ``name``        — registry key, the user-facing engine string.
    ``cache_key``   — storage key inside a ``TMBundle``; engines with the same
                      ``cache_key`` must build byte-identical caches (they are
                      prepared and maintained once, by the first registrant).
    ``needs_cache`` — False when ``prepare`` is the identity over state the
                      bundle already carries; such engines never store a cache
                      (storing one would alias ``state``'s buffers inside the
                      same pytree, which breaks donation — a donated bundle
                      must not donate one buffer through two leaves).

    Shard contract (core/distributed.py): an engine that supports clause
    sharding declares ``cache_pspec`` (how its cache pytree partitions over
    ``CLAUSE_AXIS``), builds its shard-local cache from a clause shard of the
    state via ``shard_prepare``, and evaluates partial votes via
    ``partial_scores``. ``update_cache`` is *already* shard-local: Type I/II
    feedback is clause-local given the vote, so each shard replays only its
    own events against its own cache (or, for the packed words, repacks its
    own rows) — no extra methods needed for learning.
    """

    name: str = ""
    cache_key: str = ""
    needs_cache: bool = True

    def prepare(self, cfg: TMConfig, state: TMState):
        """Build this engine's cache pytree from scratch (pure, jittable)."""
        raise NotImplementedError

    def scores(self, cfg: TMConfig, cache, x: jax.Array) -> jax.Array:
        """(B, o) inputs → (B, m) class scores from the cache alone."""
        raise NotImplementedError

    def update_cache(self, cfg: TMConfig, cache, state: TMState,
                     events: Event):
        """Absorb TA boundary crossings; default rebuilds from ``state``.

        ``state`` is the *post*-update TA state; ``events`` the include-mask
        diff that produced it (``indexing.events_from_transition``). Caches
        must have been in sync with the pre-update state — the TMBundle sync
        contract (DESIGN.md §3). The rebuild reads no event, so it stays
        exact when the buffer overflows.
        """
        del events
        return self.prepare(cfg, state)

    # -- shard contract (DESIGN.md §6) --------------------------------------

    def cache_pspec(self, cfg: TMConfig):
        """PartitionSpec pytree (same structure as the cache) placing the
        clause axis on ``CLAUSE_AXIS``. Axes whose *values* are shard-local
        (list slots, per-shard counts) tile over ``CLAUSE_AXIS`` as opaque
        blocks — the assembled global array is storage, interpreted only
        through shard_map with this same spec."""
        raise NotImplementedError(
            f"engine {self.name!r} does not declare a cache PartitionSpec; "
            "implement cache_pspec/shard_prepare/partial_scores to make it "
            "clause-shardable (DESIGN.md §6)")

    def shard_prepare(self, cfg: TMConfig, state: TMState, n_shards: int):
        """Shard-local cache from a clause shard of the state. Default:
        ``prepare`` — correct whenever cache shapes carry the clause axis
        directly (the indexed engine overrides to split list capacity)."""
        del n_shards
        return self.prepare(cfg, state)

    def partial_scores(self, cfg: TMConfig, cache, x: jax.Array,
                       pol: jax.Array) -> jax.Array:
        """(B, m) partial vote sums over this shard's clauses.

        ``pol`` is the shard's ±1 polarity slice; partials must *add* across
        shards — one psum over ``CLAUSE_AXIS`` yields the engine's global
        scores (the single (B, m) vote all-reduce).
        """
        raise NotImplementedError(
            f"engine {self.name!r} does not implement partial_scores")


def _partial_votes(clause_out: jax.Array, pol: jax.Array) -> jax.Array:
    """(B, m, n_local) clause outputs × (n_local,) ±1 polarity → (B, m)."""
    return jnp.einsum("bmn,n->bm", clause_out.astype(jnp.int32),
                      pol.astype(jnp.int32))


_REGISTRY: dict[str, EvalEngine] = {}
_CACHE_PROVIDERS: dict[str, EvalEngine] = {}


def register_engine(engine: EvalEngine) -> EvalEngine:
    """Add an engine instance to the registry (idempotent per name)."""
    if not engine.name:
        raise ValueError("engine must set a non-empty .name")
    if not engine.cache_key:
        engine.cache_key = engine.name
    _REGISTRY[engine.name] = engine
    # first registrant for a cache_key owns prepare/update for it
    _CACHE_PROVIDERS.setdefault(engine.cache_key, engine)
    return engine


def get_engine(name: str) -> EvalEngine:
    """Look up a registered engine by name (KeyError lists what exists)."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown engine {name!r}; registered: {registered_engines()}"
        ) from None


def registered_engines() -> tuple[str, ...]:
    """Registered engine names, registration order."""
    return tuple(_REGISTRY)


def cache_provider(cache_key: str) -> EvalEngine:
    """The engine that owns prepare/update for a given cache slot."""
    return _CACHE_PROVIDERS[cache_key]


# ---------------------------------------------------------------------------
# dense — exhaustive evaluation (the paper's baseline)
# ---------------------------------------------------------------------------


class DenseEngine(EvalEngine):
    """Exhaustive eval straight off the TA state; the cache *is* the state,
    so no cache is ever stored (``needs_cache=False``) — ``bundle_scores``
    falls through to the zero-cost ``prepare``."""

    name = "dense"
    needs_cache = False

    def prepare(self, cfg: TMConfig, state: TMState) -> TMState:
        return state

    def scores(self, cfg: TMConfig, cache: TMState, x: jax.Array) -> jax.Array:
        return tm.scores(cfg, cache, x)

    def update_cache(self, cfg, cache, state, events):
        del events
        return state  # zero-copy: the new state is the new cache

    def cache_pspec(self, cfg):
        # the "cache" is the TA state itself: (m, n, 2o) over clauses
        return TMState(ta_state=P(None, CLAUSE_AXIS, None))

    def partial_scores(self, cfg, cache, x, pol):
        return _partial_votes(tm.dense_clause_outputs(cfg, cache, x), pol)


# ---------------------------------------------------------------------------
# bitpack / bitpack_xla — 32×-packed include words (shared cache)
# ---------------------------------------------------------------------------


class BitpackEngine(EvalEngine):
    """32×-packed include words, evaluated through the kernel backend
    registry (``kernels/backend.py``): the ``clause_votes`` primitive
    resolves ``cfg.backend`` into the fused Pallas eval+vote kernel or its
    XLA reference body — the same resolution single-device and as the
    shard-local evaluator under shard_map (the kernel takes the shard's
    local ±1 polarity slice; partial votes add across shards, one psum).

    ``bitpack_xla`` is a registry *alias*: the same engine pinned to
    ``backend='xla'`` regardless of the config (it shares the ``bitpack``
    cache slot, so a bundle maintains the packed words once).

    The words are kept by the default ``update_cache``, a repack of the new
    state: one pass over the TA cells with no scatter or sort, cheaper than
    replaying a buffer sized for the step's worst case, and exact whatever
    the buffer dropped.
    """

    cache_key = "bitpack"
    name = "bitpack"

    def __init__(self, name: str | None = None,
                 backend: str | None = None):
        if name is not None:
            self.name = name
        self.backend = backend  # None → resolve cfg.backend

    def _votes(self, cfg: TMConfig):
        return kbackend.resolve("clause_votes", self.backend or cfg.backend)

    def prepare(self, cfg: TMConfig, state: TMState) -> jax.Array:
        return pack_bits(include_mask(cfg, state).astype(jnp.uint8))

    def cache_pspec(self, cfg):
        return P(None, CLAUSE_AXIS, None)                     # (m, n, W)

    def scores(self, cfg, cache, x):
        return self._votes(cfg)(cache, packed_literals(x),
                                clause_polarity(cfg))

    def partial_scores(self, cfg, cache, x, pol):
        return self._votes(cfg)(cache, packed_literals(x), pol)


# ---------------------------------------------------------------------------
# compact — gather over included literals (work ∝ Σ clause lengths)
# ---------------------------------------------------------------------------


class CompactEngine(EvalEngine):
    """Clause-compact transpose layout; ℓ_max is static from the config
    (``cfg.resolved_clause_capacity``), not a data-dependent host sync."""

    name = "compact"

    def prepare(self, cfg: TMConfig, state: TMState) -> indexing.CompactClauses:
        return indexing.compact(cfg, state, cfg.resolved_clause_capacity)

    def scores(self, cfg, cache, x):
        return indexing.compact_scores(cfg, cache, x)

    def update_cache(self, cfg, cache, state, events):
        del state
        return indexing.compact_apply_events(cache, events)

    def cache_pspec(self, cfg):
        return indexing.CompactClauses(
            lit_idx=P(None, CLAUSE_AXIS, None),               # (m, n, ℓ_max)
            lengths=P(None, CLAUSE_AXIS))                     # (m, n)

    def partial_scores(self, cfg, cache, x, pol):
        return _partial_votes(indexing.compact_eval(cfg, cache, x), pol)


# ---------------------------------------------------------------------------
# indexed — the paper's falsification index (Eq. 4)
# ---------------------------------------------------------------------------


class IndexedEngine(EvalEngine):
    """Inclusion lists + batched O(events) maintenance (paper §3).

    Both hot paths resolve through the kernel backend registry: scoring is
    the matmul-form Eq. 4 over the position matrix's membership mask
    (``indexed_votes`` — XLA GEMM body or the fused Pallas kernel per
    ``cfg.backend``), maintenance the batched event replay
    (``index_update``). The sequential ``indexing.apply_events`` scan stays
    as the semantics oracle, not the production route.
    """

    name = "indexed"

    def _votes(self, cfg: TMConfig):
        return kbackend.resolve("indexed_votes", cfg.backend)

    def prepare(self, cfg: TMConfig, state: TMState) -> indexing.ClauseIndex:
        return indexing.build_index(cfg, state, cfg.resolved_index_capacity)

    def scores(self, cfg, cache, x):
        return self._votes(cfg)(cache.pos, literals_from_input(x),
                                clause_polarity(cfg))

    def update_cache(self, cfg, cache, state, events):
        del state
        return indexing.index_update(cache, events, backend=cfg.backend)

    def cache_pspec(self, cfg):
        # Per-shard falsification lists: each shard owns complete lists over
        # *its own* clauses (local ids), so the falsified-union is shard-local
        # and partial counts add. lists tile capacity rows, counts tile their
        # per-shard (m, 2o) blocks — opaque storage outside shard_map.
        return indexing.ClauseIndex(
            lists=P(None, None, CLAUSE_AXIS),                 # (m, 2o, cap)
            counts=P(None, CLAUSE_AXIS),                      # (m, S·2o)
            pos=P(None, CLAUSE_AXIS, None))                   # (m, n, 2o)

    def shard_prepare(self, cfg, state, n_shards):
        cap = indexing.shard_capacity(cfg.resolved_index_capacity, n_shards)
        return indexing.build_index(cfg, state, cap)

    def partial_scores(self, cfg, cache, x, pol):
        return self._votes(cfg)(cache.pos, literals_from_input(x), pol)


register_engine(DenseEngine())
register_engine(BitpackEngine())
# registry alias: same engine + cache, backend pinned to the XLA body
register_engine(BitpackEngine(name="bitpack_xla", backend="xla"))
register_engine(CompactEngine())
register_engine(IndexedEngine())
