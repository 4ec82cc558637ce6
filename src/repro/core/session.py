"""Topology-aware TM execution: one estimator surface for every placement.

The clause-indexing paper's engines are placement-agnostic by construction
(DESIGN.md §6: the sharded unit is the whole ``TMBundle``); what was missing
was a single front door. This module is that door:

  * ``Topology`` — a declarative placement spec: how many clause shards
    (the Massively Parallel TM partitioning axis), how many data shards
    (batch axis for inference / batch-parallel learning; extra clause
    parallelism for sequential learning — see ``distributed.py``), which
    engines to maintain, and whether train steps donate their input bundle.
  * ``TMSession`` — resolves a ``Topology`` **once** into either the
    single-device jitted path (``api.train_step_jit`` / ``api._scores_jit``)
    or the shard_map path (``distributed.make_sharded_*`` over a host mesh),
    and exposes placement-transparent ``prepare`` / ``train_step`` /
    ``scores`` / ``predict``. Both resolutions are bit-exact for the same
    seed (each shard draws its own rows of the same stream), so a
    topology is a deployment detail — the property tests/test_tm_session.py
    pins.
  * ``TsetlinMachine`` — the stateful estimator facade over a session
    (init / fit / partial_fit / predict / scores / evaluate, plus the
    versioned ``save`` / ``load`` checkpoint API). ``fit`` pads a trailing
    partial batch to the compiled shape with a sample mask — no recompile,
    no dropped samples.

Serving (``launch/tm_serve.py``) and fault-tolerant training
(``runtime/tm_task.py``) drive the same session object; checkpoints persist
state + config fingerprint only (``checkpoint/tm_store.py``) and rebuild
caches on the restoring session's topology (reshard-on-restore).
"""
from __future__ import annotations

import dataclasses
from typing import Iterable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.core import api, indexing
from repro.core.api import (
    DEFAULT_ENGINE, TMBundle, init_bundle, train_step_jit)
from repro.core.engines import CLAUSE_AXIS, registered_engines
from repro.core.types import TMConfig, TMState, init_tm


@dataclasses.dataclass(frozen=True)
class ScoresLowering:
    """One padded batch shape's scores graph, staged for AOT compilation.

    Produced by ``TMSession.lower_scores`` and consumed by the serving AOT
    bucket cache (``serving/aot.py``): ``lowered.compile()`` yields the
    executable once at startup, and the hot serving loop only ever calls
    ``bind(compiled, x)`` — which closes over the (fixed) serving bundle's
    operands, so a dispatch can never retrace or recompile.

    ``x_sharding`` is the placement a ``(batch_size, n_features)`` uint8
    batch must land on before ``bind`` (None on a single-device session:
    any uncommitted array is accepted).
    """

    lowered: object            # jax.stages.Lowered
    bind: object               # (compiled, x) -> (batch_size, m) scores
    x_sharding: object | None  # NamedSharding of the batch operand (or None)
    batch_size: int
    engine: str


# AOT serving jits for the single-device path, keyed by the donate-x flag —
# module-level for the same reason as api._scores_jit: every session and
# estimator shares one XLA compilation cache.
_AOT_SCORES_JIT: dict[bool, object] = {}


def _aot_scores_jit(donate_x: bool):
    fn = _AOT_SCORES_JIT.get(donate_x)
    if fn is None:
        fn = jax.jit(api.bundle_scores, static_argnames=("engine",),
                     donate_argnums=(1,) if donate_x else ())
        _AOT_SCORES_JIT[donate_x] = fn
    return fn


@dataclasses.dataclass(frozen=True)
class Topology:
    """Declarative placement for a TM: resolved once by ``TMSession``.

    ``clause_shards``  — ways the clause axis splits over the mesh ``model``
                         axis (1 → no clause sharding).
    ``data_shards``    — ways the batch splits over the mesh ``data`` axis
                         for inference and batch-parallel learning; for
                         sequential learning the data axis instead composes
                         with the clause axis (hierarchical data×clause
                         sharding, ``distributed.make_sharded_train_step``).
    ``engines``        — engine names whose caches the bundle maintains
                         (None → every registered engine).
    ``donate``         — train steps donate the input bundle's buffers
                         (None → wherever the backend implements donation).
    ``backend``        — kernel backend the TM primitives resolve through
                         (``kernels/backend.py``): ``'auto'`` | ``'xla'`` |
                         ``'pallas'`` | ``'pallas_interpret'``; None defers
                         to ``TMConfig.backend``. Placement and kernel
                         choice are declared in one spot and resolved once.
    ``async_votes``    — K > 0 trains clause shards *asynchronously* against
                         a K-step-stale vote sum (DESIGN.md §11): no vote
                         collective inside the step, one batched all-reduce
                         per K steps refreshes the ``VoteAccumulator``.
                         0 (default) keeps the bit-exact synchronous
                         semantics. An execution knob like ``backend``:
                         checkpoints ignore it.
    """

    clause_shards: int = 1
    data_shards: int = 1
    engines: tuple[str, ...] | None = None
    donate: bool | None = None
    backend: str | None = None
    async_votes: int = 0

    def __post_init__(self):
        if self.clause_shards < 1 or self.data_shards < 1:
            raise ValueError(
                f"Topology shard counts must be >= 1, got clause_shards="
                f"{self.clause_shards}, data_shards={self.data_shards}")
        if self.async_votes < 0:
            raise ValueError(
                f"async_votes must be >= 0 (0 = synchronous), got "
                f"{self.async_votes}")
        if self.engines is not None and not isinstance(self.engines, tuple):
            object.__setattr__(self, "engines", tuple(self.engines))
        if self.backend is not None:
            from repro.kernels.backend import BACKENDS
            if self.backend not in BACKENDS:
                raise ValueError(
                    f"unknown kernel backend {self.backend!r}; one of "
                    f"{BACKENDS}")

    @property
    def n_devices(self) -> int:
        """Devices this topology occupies (``clause_shards · data_shards``)."""
        return self.clause_shards * self.data_shards

    @property
    def is_sharded(self) -> bool:
        """True when the topology needs a mesh (more than one device)."""
        return self.n_devices > 1

    def describe(self) -> dict:
        """Machine-readable placement summary (benchmarks record this)."""
        return {"clause_shards": self.clause_shards,
                "data_shards": self.data_shards,
                "devices": self.n_devices,
                "async_votes": self.async_votes}


def _topology_of_mesh(mesh, engines, donate) -> Topology:
    """Derive the Topology an explicit mesh implements."""
    clause = mesh.shape.get(CLAUSE_AXIS, 1)
    data = 1
    for a in ("pod", "data"):
        data *= mesh.shape.get(a, 1)
    return Topology(clause_shards=clause, data_shards=data,
                    engines=engines, donate=donate)


class TMSession:
    """One resolved (config × topology): placement-transparent execution.

    Resolution happens once, here: a 1-device topology binds the jitted
    single-device functions; anything larger builds (or adopts) a mesh and
    binds the shard_map factories. Every method downstream —
    ``prepare`` / ``train_step`` / ``scores`` / ``predict`` — has identical
    semantics and bit-exact results across resolutions.

    Pass ``mesh=`` to adopt an existing mesh (the trainer's, a production
    pod slice) instead of building a host mesh from the shard counts.
    """

    def __init__(self, cfg: TMConfig, topology: Topology | None = None, *,
                 mesh=None, engines: Iterable[str] | None = None,
                 parallel: bool = False, max_events: int = 4096):
        if topology is None:
            topology = Topology(
                engines=tuple(engines) if engines is not None else None)
        elif engines is not None:
            if (topology.engines is not None
                    and topology.engines != tuple(engines)):
                raise ValueError(
                    f"conflicting engines: topology says {topology.engines}, "
                    f"call says {tuple(engines)}")
            topology = dataclasses.replace(topology, engines=tuple(engines))
        if mesh is not None:
            adopted = _topology_of_mesh(mesh, topology.engines,
                                        topology.donate)
            topology = dataclasses.replace(adopted, backend=topology.backend,
                                           async_votes=topology.async_votes)
        if topology.backend is not None and topology.backend != cfg.backend:
            # the topology's kernel choice wins: everything downstream —
            # engines, the training round, the shard_map factories — reads
            # cfg.backend, so resolve the override into the config once here
            cfg = dataclasses.replace(cfg, backend=topology.backend)
        self.cfg = cfg
        self.topology = topology
        self.parallel = parallel
        self.max_events = max_events
        self.engines = (topology.engines if topology.engines is not None
                        else registered_engines())
        self._scores_fns: dict[str, object] = {}
        self._refresh = None
        self._pending_steps = 0  # steps since the last stale-vote refresh

        if not topology.is_sharded:
            if topology.async_votes > 0:
                raise ValueError(
                    f"Topology(async_votes={topology.async_votes}) needs a "
                    "sharded placement — on a single device there is no "
                    "vote collective to make asynchronous; use "
                    "clause_shards/data_shards > 1 (or async_votes=0)")
            self.mesh = None
            self.geometry = None
            self._prepare = None
            self._step = None
            return

        from repro.core import distributed  # sharded resolution only
        if mesh is None:
            from repro.launch.mesh import make_host_mesh
            try:
                mesh = make_host_mesh(data=topology.data_shards,
                                      model=topology.clause_shards)
            except RuntimeError as e:
                raise RuntimeError(
                    f"Topology(clause_shards={topology.clause_shards}, "
                    f"data_shards={topology.data_shards}) needs "
                    f"{topology.n_devices} devices: {e}") from None
        self.mesh = mesh
        # ragged clause geometry + the sequential composition rule this
        # (cfg × mesh) resolves to (DESIGN.md §9) — any shard counts compose;
        # make_sharded_train_step warns when the rule is 'replicated'
        self.geometry = distributed.geometry(cfg, mesh)
        self._prepare = distributed.make_sharded_prepare(
            cfg, mesh, engines=self.engines,
            async_votes=topology.async_votes)
        self._step = distributed.make_sharded_train_step(
            cfg, mesh, engines=self.engines, parallel=parallel,
            max_events=max_events, donate=topology.donate,
            async_votes=topology.async_votes)
        if topology.async_votes > 0:
            self._refresh = distributed.make_vote_refresh(
                cfg, mesh, parallel=parallel, donate=topology.donate)

    # -- placement ----------------------------------------------------------

    @property
    def is_sharded(self) -> bool:
        """True when this session resolved onto a mesh (shard_map path)."""
        return self.mesh is not None

    def state_sharding(self):
        """Target sharding of the bundle's ``ta_state`` (None = any).

        Under a ragged clause geometry the sharded array is the *padded*
        state (``geometry.n_padded`` clause rows), so this sharding does
        not apply to an unpadded global state — ``prepare`` pads first.
        """
        if self.mesh is None:
            return None
        from repro.core.distributed import STATE_PSPEC
        return NamedSharding(self.mesh, STATE_PSPEC.ta_state)

    def unpad_state(self, state: TMState) -> TMState:
        """Global ``(m, n_clauses, 2o)`` view of a (possibly padded) state.

        Sharded bundles carry the ragged clause layout (DESIGN.md §9);
        everything user-facing — the estimator's ``state`` property,
        checkpoints, cross-topology comparisons — goes through this view,
        so padding never leaks out of the session.
        """
        if self.geometry is None or not self.geometry.ragged_clauses:
            return state
        from repro.core import distributed
        return distributed.unpad_state(self.cfg, state)

    def describe(self) -> dict:
        """Placement summary + the resolved backend and composition rule.

        ``composition`` names the sequential-learning rule the topology
        resolved to (``composed_even`` / ``composed_ragged`` /
        ``replicated`` / ``clause_only``; ``single`` on one device,
        ``batch_parallel`` when the session runs the parallel learning
        mode) — recorded in BENCH_tm_serve.json topology metadata.
        ``shard_rows`` is the per-clause-shard row census
        (``[{shard, real_rows, pad_rows}]``): where the ragged clause
        padding actually lands (all of it on the trailing shard(s), §9).
        """
        from repro.kernels.backend import resolve_backend
        d = self.topology.describe()
        d["sharded"] = self.is_sharded
        d["backend"] = resolve_backend(self.cfg.backend)
        if self.geometry is None:
            d["composition"] = "single"
            d["shard_rows"] = [{"shard": 0, "real_rows": self.cfg.n_clauses,
                                "pad_rows": 0}]
        else:
            d["composition"] = ("batch_parallel" if self.parallel
                                else self.geometry.composition)
            d["shard_rows"] = self.geometry.shard_rows()
        return d

    # -- bundle lifecycle ---------------------------------------------------

    def prepare(self, state: TMState) -> TMBundle:
        """Bundle with this session's caches built from ``state`` (placed
        per the topology; sharded caches are built shard-locally)."""
        if self._prepare is not None:
            return self._prepare(state)
        return init_bundle(self.cfg, engines=self.engines, state=state)

    def init_bundle(self, rng: jax.Array | None = None) -> TMBundle:
        """Freshly initialised bundle (all TAs exclude), placed and cached
        per this session's topology."""
        return self.prepare(init_tm(self.cfg, rng))

    # -- execution ----------------------------------------------------------

    def train_step(self, bundle: TMBundle, xs, ys, rng,
                   mask=None) -> TMBundle:
        """One learning step (all maintained caches stay in sync). The
        input bundle is donated when the topology says so — do not read it
        afterwards.

        Under ``async_votes=K`` the step itself performs no vote
        collective; the session counts steps and chains the stale-vote
        refresh (one batched all-reduce) onto every K-th step — the
        cadence is host-side state, so the step executable stays
        collective-clean for the dry-run's HLO assertions.
        """
        if self._step is not None:
            d = self.topology.data_shards
            if self.parallel and xs.shape[0] % d:
                raise ValueError(
                    f"batch size {xs.shape[0]} does not divide over "
                    f"data_shards={d} (batch-parallel learning shards the "
                    "batch); pick a divisible batch_size")
            bundle = self._step(bundle, xs, ys, rng, mask)
            if self._refresh is not None:
                self._pending_steps += 1
                if self._pending_steps >= self.topology.async_votes:
                    bundle = self._refresh(bundle)
                    self._pending_steps = 0
            return bundle
        return train_step_jit(bundle, xs, ys, rng, mask,
                              parallel=self.parallel,
                              max_events=self.max_events,
                              donate=self.topology.donate)

    def refresh_votes(self, bundle: TMBundle) -> TMBundle:
        """Force a stale-vote refresh now (resets the K-step cadence).

        No-op outside async mode. Useful before an accuracy read or a
        checkpoint when mid-window staleness matters; also drains the
        accumulated per-rank overflow counts into ``bundle.event_overflow``
        (between refreshes the bundle's counter deliberately lags —
        overflow accounting rides the refresh collective, never a per-step
        psum).
        """
        if self._refresh is None:
            return bundle
        self._pending_steps = 0
        return self._refresh(bundle)

    def _sharded_scores_fn(self, engine: str):
        """Memoised ``make_sharded_scores`` wrapper for one engine."""
        fn = self._scores_fns.get(engine)
        if fn is None:
            from repro.core.distributed import make_sharded_scores
            fn = make_sharded_scores(self.cfg, self.mesh, engine=engine)
            self._scores_fns[engine] = fn
        return fn

    def scores(self, bundle: TMBundle, x, *,
               engine: str = DEFAULT_ENGINE) -> jax.Array:
        """(B, o) inputs → (B, m) class scores through a registry engine
        (the single-device jitted graph, or the sharded one-all-reduce
        scores path when this session holds a mesh)."""
        if self.mesh is None:
            return api._scores_jit(bundle, x, engine=engine)
        return self._sharded_scores_fn(engine)(bundle, x)

    def fingerprint(self) -> str:
        """Short stable id of (config × resolved placement × backend).

        Part of the AOT serving cache key (``serving/aot.py``): two
        sessions share compiled bucket executables only when their configs
        fingerprint-match *and* they resolved to the same placement,
        composition rule, and kernel backend. Built from the checkpoint
        config fingerprint (which deliberately ignores ``backend``) plus
        ``describe()`` (which records the resolved backend), so a backend
        switch changes the serving key without invalidating checkpoints.
        """
        import hashlib

        from repro.checkpoint.tm_store import config_fingerprint
        blob = repr(sorted(self.describe().items())).encode()
        blob += bytes(bytearray(config_fingerprint(self.cfg)))
        return hashlib.sha256(blob).hexdigest()[:16]

    def lower_scores(self, bundle: TMBundle, batch_size: int, *,
                     engine: str = DEFAULT_ENGINE,
                     donate_x: bool = False) -> ScoresLowering:
        """Stage the scores graph for one padded batch shape (AOT hook).

        The returned ``ScoresLowering`` separates the three serving phases
        the hot loop must never mix: ``lowered`` (trace + lower, done
        here), ``lowered.compile()`` (done once per bucket by
        ``serving/aot.py``, timed separately), and ``bind(compiled, x)``
        (the only thing a dispatch calls). ``bind`` closes over *this*
        bundle's operands — the sharded resolution binds the prepared
        shard-local cache (or the TA state for cache-less engines) with
        explicit in/out shardings, the single-device resolution binds the
        bundle through the shared AOT jit. ``donate_x`` donates the batch
        operand's buffer to the executable (pass
        ``api.resolve_donate(None)`` to donate wherever the backend
        implements it).
        """
        x_spec = jax.ShapeDtypeStruct((batch_size, self.cfg.n_features),
                                      jnp.uint8)
        if self.mesh is None:
            fn = _aot_scores_jit(donate_x)
            lowered = fn.lower(bundle, x_spec, engine=engine)

            def bind(compiled, x):
                return compiled(bundle, x)

            return ScoresLowering(lowered=lowered, bind=bind,
                                  x_sharding=None, batch_size=batch_size,
                                  engine=engine)

        sfn = self._sharded_scores_fn(engine)
        operand = sfn.operand(bundle)
        x_sharding = NamedSharding(self.mesh, sfn.bspec)
        x_spec = jax.ShapeDtypeStruct(x_spec.shape, x_spec.dtype,
                                      sharding=x_sharding)
        lowered = sfn.aot_jit(donate_x).lower(operand, sfn.pol, x_spec)

        def bind(compiled, x):
            return compiled(operand, sfn.pol, x)

        return ScoresLowering(lowered=lowered, bind=bind,
                              x_sharding=x_sharding, batch_size=batch_size,
                              engine=engine)

    def predict(self, bundle: TMBundle, x, *,
                engine: str = DEFAULT_ENGINE) -> jax.Array:
        """(B, o) inputs → (B,) argmax class through a registry engine."""
        if self.mesh is None:
            return api._predict_jit(bundle, x, engine=engine)
        return jnp.argmax(self.scores(bundle, x, engine=engine), axis=-1)

    # -- checkpointing (schema v1: state + config fingerprint) --------------

    def save(self, directory, bundle: TMBundle, *, step: int = 0,
             keep: int = 3, blocking: bool = True) -> None:
        """Write a schema-v1 checkpoint of the bundle's global TA state.

        Always the unpadded ``(m, n_clauses, 2o)`` view — checkpoints are
        topology-free, so a state saved under a ragged placement loads
        bit-exactly anywhere (and vice versa)."""
        from repro.checkpoint import tm_store
        ta = self.unpad_state(bundle.state).ta_state
        tm_store.save_tm(directory, self.cfg, ta,
                         step=step, keep=keep, blocking=blocking)

    def restore(self, directory, *, step: int | None = None):
        """(bundle, step) from a schema-v1 checkpoint: the TA state lands on
        this session's placement and every cache rebuilds on this topology
        (reshard-on-restore — caches are never persisted). Under a ragged
        clause geometry the checkpointed global state cannot land directly
        on the mesh (the sharded layout is the padded one), so it loads
        unplaced and ``prepare`` pads + places it."""
        from repro.checkpoint import tm_store
        like = jax.ShapeDtypeStruct(
            (self.cfg.n_classes, self.cfg.n_clauses, self.cfg.n_literals),
            self.cfg.state_dtype)
        sharding = (None if (self.geometry is not None
                             and self.geometry.ragged_clauses)
                    else self.state_sharding())
        ta, step = tm_store.load_tm(directory, self.cfg, like, step=step,
                                    sharding=sharding)
        return self.prepare(TMState(ta_state=ta)), step


class TsetlinMachine:
    """Estimator facade over a ``TMSession``.

    >>> machine = TsetlinMachine(cfg, topology=Topology(clause_shards=4))
    >>> machine.init().fit(xs, ys, epochs=3, batch_size=128)
    >>> machine.predict(x_test, engine="indexed")

    The topology is transparent: the same script runs single-device, clause
    sharded, or data×clause sharded, bit-exactly. Every heavy call delegates
    to the session's jitted pure functions; the facade only owns the bundle
    reference and the RNG chain.
    """

    def __init__(
        self,
        cfg: TMConfig,
        *,
        topology: Topology | None = None,
        engines: Iterable[str] | None = None,
        parallel: bool = False,
        max_events_per_batch: int = 4096,
        seed: int = 0,
    ):
        self.session = TMSession(cfg, topology, engines=engines,
                                 parallel=parallel,
                                 max_events=max_events_per_batch)
        self.cfg = self.session.cfg  # topology backend override resolved in
        self.engines = self.session.engines
        self.parallel = parallel
        self.max_events_per_batch = max_events_per_batch
        self._key = jax.random.key(seed)
        self.bundle: TMBundle | None = None

    @property
    def topology(self) -> Topology:
        """The placement this machine's session resolved."""
        return self.session.topology

    # -- lifecycle ----------------------------------------------------------

    def init(self, rng: jax.Array | None = None) -> "TsetlinMachine":
        """(Re)initialise the bundle on this machine's topology."""
        self.bundle = self.session.init_bundle(rng)
        return self

    def _ensure_bundle(self) -> TMBundle:
        if self.bundle is None:
            self.init()
        return self.bundle

    def _next_key(self, rng: jax.Array | None) -> jax.Array:
        if rng is not None:
            return rng
        self._key, sub = jax.random.split(self._key)
        return sub

    # -- learning -----------------------------------------------------------

    def partial_fit(self, xs, ys, rng: jax.Array | None = None, *,
                    mask=None) -> "TsetlinMachine":
        """One train step over a batch (all maintained caches kept in sync).
        ``mask`` (B,) bool marks valid rows — padded rows apply no update."""
        bundle = self._ensure_bundle()
        self.bundle = self.session.train_step(
            bundle, xs, ys, self._next_key(rng), mask)
        return self

    def fit(self, xs, ys, *, epochs: int = 1, batch_size: int | None = None,
            rng: jax.Array | None = None) -> "TsetlinMachine":
        """Epoch loop of ``partial_fit``; fixed-size minibatches when
        ``batch_size`` is set. A trailing partial batch pads to the compiled
        shape with a sample mask — every step reuses one compiled graph and
        every sample trains (padded rows are masked out)."""
        n = int(xs.shape[0])
        if batch_size is not None and n < batch_size:
            raise ValueError(
                f"batch_size={batch_size} exceeds dataset size "
                f"{n}: fit would perform zero steps")
        key = self._next_key(rng)
        for _ in range(epochs):
            if batch_size is None:
                key, sub = jax.random.split(key)
                self.partial_fit(xs, ys, sub)
                continue
            for start in range(0, n, batch_size):
                key, sub = jax.random.split(key)
                k = min(batch_size, n - start)
                xb, yb = xs[start:start + k], ys[start:start + k]
                mask = None  # full batches skip the masking work entirely
                if k < batch_size:  # pad to the compiled shape, mask the rest
                    pad = batch_size - k
                    xb = jnp.concatenate(
                        [jnp.asarray(xb),
                         jnp.zeros((pad,) + tuple(xs.shape[1:]),
                                   jnp.asarray(xb).dtype)])
                    yb = jnp.concatenate(
                        [jnp.asarray(yb),
                         jnp.zeros((pad,), jnp.asarray(yb).dtype)])
                    mask = jnp.arange(batch_size) < k
                self.partial_fit(xb, yb, sub, mask=mask)
        return self

    # -- inference ----------------------------------------------------------

    def scores(self, xs, *, engine: str = DEFAULT_ENGINE) -> jax.Array:
        """(B, o) inputs → (B, m) class scores through a registry engine."""
        return self.session.scores(self._ensure_bundle(), xs, engine=engine)

    def predict(self, xs, *, engine: str = DEFAULT_ENGINE) -> jax.Array:
        """(B, o) inputs → (B,) argmax class through a registry engine."""
        return self.session.predict(self._ensure_bundle(), xs, engine=engine)

    def evaluate(self, xs, ys, *, engine: str = DEFAULT_ENGINE) -> float:
        """Mean prediction accuracy of ``xs`` against labels ``ys``."""
        return float(jnp.mean(
            (self.predict(xs, engine=engine) == ys).astype(jnp.float32)))

    # -- state access / persistence -----------------------------------------

    @property
    def event_overflow(self) -> int:
        """Cache-sync events dropped since the bundle was prepared.

        Non-zero means ``max_events_per_batch`` was too small for some step
        and the engine caches are stale mirrors — a config error. Checking
        costs one scalar device read, so callers can assert
        ``machine.event_overflow == 0`` after every step (or epoch) instead
        of sizing the buffer to the ``n_classes·n_clauses·n_literals``
        worst case up front. Note the buffer is per clause shard
        (DESIGN.md §6): a sharded topology holds ``clause_shards ×
        max_events_per_batch`` crossings in total, so size the buffer for
        the placement with the *fewest* clause shards you intend to run —
        a limit that held on ``Topology(clause_shards=4)`` may overflow on
        ``Topology(1)``.
        """
        bundle = self.bundle
        if bundle is None or bundle.event_overflow is None:
            return 0
        return int(jax.device_get(bundle.event_overflow))

    @property
    def state(self) -> TMState:
        """The global ``(m, n_clauses, 2o)`` TA state (never padded: any
        ragged clause-axis padding the sharded layout carries is stripped,
        so states compare bit-exactly across topologies)."""
        return self.session.unpad_state(self._ensure_bundle().state)

    @property
    def index(self) -> indexing.ClauseIndex:
        """The paper's clause index (shard-local layout when sharded)."""
        return self._ensure_bundle().index

    def save(self, directory, *, step: int = 0, keep: int = 3,
             blocking: bool = True) -> "TsetlinMachine":
        """Versioned checkpoint (schema v1): TA state + config fingerprint
        only. Engine caches are derived data and never persist — ``load``
        rebuilds them on the loading machine's topology."""
        self.session.save(directory, self._ensure_bundle(), step=step,
                          keep=keep, blocking=blocking)
        return self

    @classmethod
    def load(cls, directory, cfg: TMConfig, *,
             topology: Topology | None = None, step: int | None = None,
             **kwargs) -> "TsetlinMachine":
        """Restore onto any topology: the checkpointed state reshards to the
        new placement and caches rebuild there. Raises
        ``checkpoint.CheckpointMismatch`` when ``cfg`` does not fingerprint-
        match the checkpoint."""
        machine = cls(cfg, topology=topology, **kwargs)
        machine.bundle, _ = machine.session.restore(directory, step=step)
        return machine
