"""Jit-native TM estimator API: ``TMBundle`` + ``TsetlinMachine``.

Layering (DESIGN.md):

  * ``TMBundle`` — a registered pytree bundling the static ``TMConfig``
    (treedef aux data, so jit re-traces per config, never per state) with the
    learnable ``TMState`` and the per-``cache_key`` engine caches. One value
    carries everything needed to train *and* serve through any engine.
  * ``train_step(bundle, xs, ys, rng) -> bundle`` — a pure function: dense
    TA feedback, include-mask diff into a fixed-shape event buffer, then
    every cache in the bundle absorbs the events incrementally through its
    registry provider. ``jax.jit``s end-to-end; no Python-level mutation, no
    host sync inside the step. ``train_step_jit`` donates the input bundle
    (on backends that support donation) so TA states update in place.
The estimator facade (``TsetlinMachine``) and the topology resolution layer
(``Topology`` / ``TMSession``) live in ``core/session.py``; this module is
the pure single-device substrate both paths share.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Iterable

import jax
import jax.numpy as jnp

from repro.core import indexing, scopes, tm
from repro.core.engines import cache_provider, get_engine, registered_engines
from repro.core.types import TMConfig, TMState, include_mask, init_tm

DEFAULT_ENGINE = "indexed"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class TMBundle:
    """Static config + TA state + engine caches, as one jit-friendly pytree.

    ``event_overflow`` is the cumulative count of cache-sync events dropped
    by the fixed-shape buffer since the bundle was prepared (None before any
    training). It stays on device — reading it costs one scalar transfer —
    and non-zero means the caches are stale: raise ``max_events`` instead of
    sizing it to the worst case blindly (``indexing.EventBuffer``). The
    buffer is per clause shard, so the threshold the counter reflects scales
    with ``clause_shards`` — size ``max_events`` for the least-sharded
    placement a state will run on.
    """

    cfg: TMConfig
    state: TMState
    caches: dict[str, Any]
    event_overflow: jax.Array | None = None

    def tree_flatten(self):
        """Pytree protocol: leaves = (state, caches, overflow), aux = cfg."""
        return ((self.state, self.caches, self.event_overflow), self.cfg)

    @classmethod
    def tree_unflatten(cls, cfg, children):
        """Pytree protocol: rebuild from ``tree_flatten``'s output."""
        state, caches, event_overflow = children
        return cls(cfg=cfg, state=state, caches=caches,
                   event_overflow=event_overflow)

    @property
    def index(self) -> indexing.ClauseIndex:
        """The paper's clause index (present when the indexed engine is on)."""
        return self.caches["indexed"]


def cache_keys_for(engine_names: Iterable[str] | None = None) -> tuple[str, ...]:
    """Distinct cache slots the named engines need (``None`` → all registered).

    Cache-less engines (``needs_cache=False``) read ``bundle.state`` directly
    and contribute no slot. Public because the sharded layer
    (``core/distributed.py``) builds shard-local caches for the same slots.
    """
    names = (tuple(engine_names) if engine_names is not None
             else registered_engines())
    keys: dict[str, None] = {}
    for name in names:
        eng = get_engine(name)
        if eng.needs_cache:  # cache-less engines read bundle.state directly
            keys.setdefault(eng.cache_key, None)
    return tuple(keys)


def init_bundle(
    cfg: TMConfig,
    *,
    engines: Iterable[str] | None = None,
    state: TMState | None = None,
    rng: jax.Array | None = None,
) -> TMBundle:
    """Fresh bundle with caches prepared for the requested engines.

    ``engines=None`` prepares every registered engine — each *distinct*
    ``cache_key`` is built once (``bitpack``/``bitpack_xla`` share).
    """
    names = tuple(engines) if engines is not None else registered_engines()
    state = state if state is not None else init_tm(cfg, rng)
    caches = {key: cache_provider(key).prepare(cfg, state)
              for key in cache_keys_for(names)}
    return TMBundle(cfg=cfg, state=state, caches=caches,
                    event_overflow=jnp.zeros((), jnp.int32))


# cache_keys whose on-the-fly rebuild has already been warned about once —
# a missing slot silently rebuilding per call is a config smell (the engine
# should be in the bundle's engines=), but it is not an error.
_REBUILD_WARNED: set[str] = set()


def bundle_scores(
    bundle: TMBundle, x: jax.Array, *, engine: str = DEFAULT_ENGINE
) -> jax.Array:
    """(B, o) → (B, m) scores via a registered engine (pure, jittable).

    Uses the bundle's maintained cache when present; otherwise prepares one
    on the fly (still pure — just does rebuild work per call, and warns once
    per cache slot so the rebuild cost never hides in a serving loop).
    """
    eng = get_engine(engine)
    cache = bundle.caches.get(eng.cache_key)
    if cache is None:
        if eng.needs_cache and eng.cache_key not in _REBUILD_WARNED:
            _REBUILD_WARNED.add(eng.cache_key)
            warnings.warn(
                f"bundle_scores(engine={engine!r}): cache slot "
                f"{eng.cache_key!r} is not maintained in this bundle "
                f"(slots: {tuple(bundle.caches)}); rebuilding it on every "
                "call — include the engine in the bundle's engines= to "
                "maintain it incrementally (warned once per slot)",
                RuntimeWarning, stacklevel=2)
        cache = eng.prepare(bundle.cfg, bundle.state)
    return eng.scores(bundle.cfg, cache, x)


def bundle_predict(
    bundle: TMBundle, x: jax.Array, *, engine: str = DEFAULT_ENGINE
) -> jax.Array:
    """(B, o) → (B,) argmax class via a registered engine (pure, jittable)."""
    return jnp.argmax(bundle_scores(bundle, x, engine=engine), axis=-1)


def replay_events(cfg: TMConfig, caches: dict, old_inc: jax.Array,
                  new_state: TMState, max_events: int
                  ) -> tuple[dict, indexing.EventBuffer]:
    """The tail every topology's step shares: diff the include masks into a
    counted event buffer (``tm.events``), then every cache absorbs the
    events through its provider (``tm.cache_sync``). Returns the new caches
    and the buffer, whose ``overflow`` the caller accumulates.

    The packed words (``bitpack``) are repacked from ``new_state`` and read
    no event, so they are exact even when the buffer overflows; in a
    bundle with no other cache only the buffer's ``overflow`` is live, and
    XLA drops the selection's cumsums, slot scatter and sort."""
    with jax.named_scope(scopes.EVENTS):
        buf = indexing.events_from_transition(
            old_inc, include_mask(cfg, new_state), max_events)
    with jax.named_scope(scopes.CACHE_SYNC):
        caches = {key: cache_provider(key).update_cache(
                      cfg, cache, new_state, buf.events)
                  for key, cache in caches.items()}
    return caches, buf


def train_step(
    bundle: TMBundle,
    xs: jax.Array,
    ys: jax.Array,
    rng: jax.Array,
    mask: jax.Array | None = None,
    *,
    parallel: bool = False,
    max_events: int = 4096,
) -> TMBundle:
    """One learning step over a batch; every engine cache stays in sync.

    Pure function of its inputs: dense Type I/II feedback (sequential scan,
    or the batch-parallel approximation when ``parallel=True``), then the
    include-mask diff replays into each cache as a fixed-shape masked event
    buffer (≤ ``max_events`` boundary crossings per batch — overflow drops
    events and is a config error). Dropped events are *counted* into the
    returned bundle's ``event_overflow``, so callers size ``max_events`` to
    the expected load and assert the counter stays 0 instead of paying the
    ``n_classes · n_clauses · n_literals`` worst case up front (cf. the
    examples).

    ``mask`` (B,) bool marks valid samples: padded rows consume their
    per-sample randomness but apply no update, so a trailing partial batch
    can pad to the compiled shape without a recompile and without training
    on garbage (the ``TsetlinMachine.fit`` padding contract).

    The phases run under the scope names of ``core/scopes.py``, as in the
    sharded bodies: ``tm.feedback`` (the update, ``tm.draws`` inside it),
    then ``replay_events`` (``tm.events``, ``tm.cache_sync``).
    """
    cfg = bundle.cfg
    with jax.named_scope(scopes.EVENTS):
        old_inc = include_mask(cfg, bundle.state)
    update = (tm.update_batch_parallel if parallel
              else tm.update_batch_sequential)
    with jax.named_scope(scopes.FEEDBACK):
        new_state = update(cfg, bundle.state, xs, ys, rng, mask=mask)
    caches, buf = replay_events(cfg, bundle.caches, old_inc, new_state,
                                max_events)
    overflow = buf.overflow
    if bundle.event_overflow is not None:
        overflow = overflow + bundle.event_overflow
    return TMBundle(cfg=cfg, state=new_state, caches=caches,
                    event_overflow=overflow)


# Donation updates TA states/caches in place on accelerators; the CPU backend
# does not implement buffer donation (XLA warns and copies). The decision is
# made lazily per donate flag at first call — resolving it at import time
# would both force backend initialization as an import side effect and freeze
# the choice before the program can configure its platform. Keyed by the
# resolved donate flag so ``Topology(donate=...)`` overrides share the cache.
_TRAIN_STEP_JIT: dict[bool, Any] = {}


def resolve_donate(donate: bool | None) -> bool:
    """``None`` → donate wherever the backend implements it (not CPU)."""
    return jax.default_backend() != "cpu" if donate is None else donate


def train_step_jit(bundle, xs, ys, rng, mask=None, *, parallel: bool = False,
                   max_events: int = 4096, donate: bool | None = None):
    """``train_step`` under ``jax.jit``, donating the input bundle on
    backends that implement donation (or per the explicit ``donate``
    override). NOTE: where donation applies (GPU/TPU), the input bundle's
    buffers are consumed — do not read it after the call; use the pure
    ``train_step`` if you need both."""
    donate = resolve_donate(donate)
    fn = _TRAIN_STEP_JIT.get(donate)
    if fn is None:
        fn = jax.jit(train_step, static_argnames=("parallel", "max_events"),
                     donate_argnums=(0,) if donate else ())
        _TRAIN_STEP_JIT[donate] = fn
    return fn(bundle, xs, ys, rng, mask, parallel=parallel,
              max_events=max_events)


# module-level so the XLA compilation cache is shared across sessions and
# estimator instances (a freshly loaded machine reuses the compiled graphs)
_scores_jit = jax.jit(bundle_scores, static_argnames=("engine",))
_predict_jit = jax.jit(bundle_predict, static_argnames=("engine",))
