"""Clause-sharded TMBundle execution — TM at datacenter scale (beyond-paper).

The paper targets one CPU. The Massively Parallel TM line (Abeyrathna et
al., 2020) shows the scaling recipe: partition *clauses* across workers,
evaluate shard-locally, reduce the per-class vote once. This module is that
recipe over the PR-1 engine registry, so the sharded unit is the whole
``TMBundle`` — TA state *and* every engine cache — not a bare ``ta_state``:

  * every ``EvalEngine`` declares how its cache partitions over the mesh
    clause axis (``cache_pspec``), builds its shard-local cache from a
    clause shard of the state (``shard_prepare``), and evaluates partial
    votes (``partial_scores``);
  * ``make_sharded_scores`` psums the partials over ``CLAUSE_AXIS`` — the
    single (B, m) vote all-reduce, the *only* collective in the lowered HLO
    (asserted by ``launch/dryrun.py --tm``); batch shards over the data/pod
    axes with no communication at all;
  * ``make_sharded_train_step`` runs dense Type I/II feedback on each
    shard's clause slice (feedback is clause-local given the vote — the
    vote psum inside ``tm._class_round`` is again the only collective),
    then diffs the *local* include mask and replays the events into the
    shard-local caches: event-driven cache sync never leaves the shard.

Randomness: each shard draws only its own clause rows of the same uniform
stream (``tm.uniform_rows``: the counters of the full draw's rows, not the
full draw), so no chip produces another chip's uniforms and sharded
training stays **bit-exact** with the single-device path — the property
tests/test_tm_sharded.py pins for every registered engine on a forced
8-device host mesh.

Ragged geometry (DESIGN.md §9): *any* ``(data_shards, clause_shards,
n_clauses)`` is a first-class topology. The clause axis pads up to
``clause_shards · ⌈n_clauses/clause_shards⌉`` rows (``ClauseGeometry``),
and under sequential hierarchical data×clause composition each data rank
owns a zero-padded sub-slice of its clause shard sized
``⌈n_local/data_shards⌉``. Padding rows are *inert by construction*: they
carry sign-0 polarity (zero vote contribution through every engine and
kernel backend), are excluded from the feedback update gate
(``tm`` ``clause_mask`` — the zero ``ta_update`` mask), and the trailing
sub-slice padding is discarded by the reassembly slice, so votes psum and
state reassembly stay bit-exact and all-reduce-only. Only when
``data_shards`` exceeds the per-shard clause count does the sequential
step fall back to batch replication (``composition_rule='replicated'``,
warned once) — there is no clause row left to hand each data rank.

Shard-local cache layouts: caches whose arrays carry the clause axis
(packed words, compact rows, the position matrix) tile into the global
array exactly; per-shard structures with no clause axis of their own (the
index's lists capacity rows and counts) tile as opaque blocks along
``CLAUSE_AXIS`` — the assembled global array is storage, only ever
interpreted through shard_map with the engine's declared spec. The indexed
engine's shard therefore owns complete falsification lists over *its own*
clauses (local ids, dense under padding), which is what makes the
falsified-union shard-local and the partial votes additive — and since the
shard's position-matrix slice carries the same membership information
(``pos != NA`` ⇔ local include), the matmul-form Eq. 4 body
(``indexed_votes``, DESIGN.md §11) evaluates the shard's partial votes
with no list walk at all; batched index maintenance (``index_update``)
replays each shard's own event buffer shard-locally, exactly like the
scan it replaced.
"""
from __future__ import annotations

import dataclasses
import math
import warnings

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import scopes, tm
from repro.core.api import (
    DEFAULT_ENGINE, TMBundle, cache_keys_for, replay_events, resolve_donate)
from repro.core.engines import CLAUSE_AXIS, cache_provider, get_engine
from repro.core.types import (
    TMConfig, TMState, clause_polarity, include_mask)

STATE_PSPEC = TMState(ta_state=P(None, CLAUSE_AXIS, None))

# Sequential-composition rule names (DESIGN.md §9 resolution table); recorded
# by ``dryrun --tm`` and in BENCH_tm_serve.json topology metadata.
COMPOSED_EVEN = "composed_even"      # n_local divides by data_shards
COMPOSED_RAGGED = "composed_ragged"  # ragged sub-slices (zero-padded)
REPLICATED = "replicated"            # data_shards > n_local: PR-2 fallback
CLAUSE_ONLY = "clause_only"          # data_shards == 1: nothing to compose


@dataclasses.dataclass(frozen=True)
class ClauseGeometry:
    """Ragged clause-axis geometry of one ``(cfg × mesh)`` resolution.

    The clause axis pads to ``n_padded = clause_shards · n_local`` rows
    (``n_local = ⌈n_clauses/clause_shards⌉``); rows ``>= n_clauses`` are
    padding, all owned by the trailing shard(s). Under sequential
    data×clause composition each data rank owns ``n_sub =
    ⌈n_local/data_shards⌉`` rows of its shard's (re-padded) slice.
    ``composition`` names the sequential-learning rule that fired —
    ``composed_even`` / ``composed_ragged`` / ``replicated`` /
    ``clause_only`` (DESIGN.md §9).
    """

    n_clauses: int
    clause_shards: int
    data_shards: int
    n_local: int
    n_padded: int
    n_sub: int
    composition: str

    @property
    def ragged_clauses(self) -> bool:
        """True when the global clause axis itself carries padding rows."""
        return self.n_padded != self.n_clauses

    @property
    def composes(self) -> bool:
        """True when sequential learning splits clause work over data ranks."""
        return self.composition in (COMPOSED_EVEN, COMPOSED_RAGGED)

    @property
    def n_sub_padded(self) -> int:
        """Per-shard clause rows after sub-slice padding (≥ ``n_local``)."""
        return self.data_shards * self.n_sub if self.composes else self.n_local

    def shard_rows(self) -> list[dict]:
        """Per-clause-shard row census: ``[{shard, real_rows, pad_rows}]``.

        Padding lands entirely on the trailing shard(s) (§9), so shard ``i``
        owns ``clamp(n_clauses − i·n_local, 0, n_local)`` real rows. Recorded
        in ``TMSession.describe()`` → BENCH_tm_serve.json topology metadata —
        the observability half of the carried-over padding-balance item.
        """
        rows = []
        for i in range(self.clause_shards):
            real = min(max(self.n_clauses - i * self.n_local, 0), self.n_local)
            rows.append({"shard": i, "real_rows": real,
                         "pad_rows": self.n_local - real})
        return rows


def clause_geometry(n_clauses: int, clause_shards: int,
                    data_shards: int) -> ClauseGeometry:
    """Resolve the ragged geometry + sequential composition rule (§9).

    Pure in its three integers, so the resolution table is unit-testable
    without devices; ``geometry`` wraps it for a mesh.
    """
    n_local = -(-n_clauses // clause_shards)
    n_padded = clause_shards * n_local
    if data_shards <= 1:
        rule, n_sub = CLAUSE_ONLY, n_local
    elif n_local % data_shards == 0:
        rule, n_sub = COMPOSED_EVEN, n_local // data_shards
    elif data_shards <= n_local:
        rule, n_sub = COMPOSED_RAGGED, -(-n_local // data_shards)
    else:  # more data ranks than clause rows: no sub-slice to hand out
        rule, n_sub = REPLICATED, n_local
    return ClauseGeometry(
        n_clauses=n_clauses, clause_shards=clause_shards,
        data_shards=data_shards, n_local=n_local, n_padded=n_padded,
        n_sub=n_sub, composition=rule)


def geometry(cfg: TMConfig, mesh) -> ClauseGeometry:
    """``clause_geometry`` of a config on a concrete mesh."""
    shards = clause_shards(mesh)
    baxes = batch_axes(mesh)
    d = math.prod(mesh.shape[a] for a in baxes) if baxes else 1
    return clause_geometry(cfg.n_clauses, shards, d)


def batch_axes(mesh) -> tuple[str, ...]:
    """Mesh axes the batch shards over (pod-major, matching P ordering)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def clause_shards(mesh) -> int:
    """Size of the mesh clause axis; raises when the mesh has none."""
    if CLAUSE_AXIS not in mesh.axis_names:
        raise ValueError(
            f"mesh {mesh.axis_names} has no {CLAUSE_AXIS!r} axis to shard "
            "clauses over")
    return mesh.shape[CLAUSE_AXIS]


def _pad_rows(arr: jax.Array, axis: int, size: int, value) -> jax.Array:
    """Pad ``arr`` along ``axis`` up to ``size`` rows with ``value``."""
    pad = size - arr.shape[axis]
    if pad == 0:
        return arr
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, pad)
    return jnp.pad(arr, widths, constant_values=value)


def pad_state(cfg: TMConfig, state: TMState, n_padded: int) -> TMState:
    """Pad the clause axis of a global state to the sharded layout (§9).

    Padding rows sit at state ``n_states`` (every TA excludes ⇒ empty
    clause): their include mask is all-zero, so every engine cache built
    from them is empty and the event diff never sees them; the sharded
    train step freezes them via the clause mask, so the invariant persists.
    Idempotent on an already-padded state.
    """
    n = state.ta_state.shape[1]
    if n == n_padded:
        return state
    if n != cfg.n_clauses:
        raise ValueError(
            f"state has {n} clause rows; expected n_clauses="
            f"{cfg.n_clauses} (unpadded) or {n_padded} (padded)")
    return TMState(ta_state=_pad_rows(
        state.ta_state, 1, n_padded, cfg.n_states))


def unpad_state(cfg: TMConfig, state: TMState) -> TMState:
    """Drop clause-axis padding rows: the global ``(m, n_clauses, 2o)`` view."""
    if state.ta_state.shape[1] == cfg.n_clauses:
        return state
    return TMState(ta_state=state.ta_state[:, :cfg.n_clauses, :])


def bundle_pspecs(cfg: TMConfig, engines=None):
    """(state_pspec, {cache_key: cache_pspec}) for a sharded bundle."""
    return STATE_PSPEC, {key: cache_provider(key).cache_pspec(cfg)
                         for key in cache_keys_for(engines)}


def _sharded_polarity(cfg: TMConfig, mesh) -> jax.Array:
    """Global ±1 polarity, zero-padded to the ragged clause layout.

    Sign 0 is the padding convention every evaluator honours for free: a
    padding clause's output × 0 contributes nothing to any partial vote,
    whether it flows through an XLA body, the fused Pallas votes kernel, or
    the falsification index (empty clauses never enter a list).
    """
    geom = geometry(cfg, mesh)
    pol = _pad_rows(clause_polarity(cfg), 0, geom.n_padded, 0)
    return jax.device_put(pol, NamedSharding(mesh, P(CLAUSE_AXIS)))


def make_sharded_prepare(cfg: TMConfig, mesh, *, engines=None):
    """``(TMState) -> TMBundle`` with shard-local caches for every engine.

    The state pads to the ragged clause layout and lands clause-sharded
    (``STATE_PSPEC``); each distinct cache slot is built *on its shard*
    from the local state slice — no device ever materialises a full cache.
    """
    geom = geometry(cfg, mesh)
    shards = geom.clause_shards
    keys = cache_keys_for(engines)
    state_sh = NamedSharding(mesh, STATE_PSPEC.ta_state)
    _, cache_specs = bundle_pspecs(cfg, engines)

    def local_fn(state_l: TMState):
        return {k: cache_provider(k).shard_prepare(cfg, state_l, shards)
                for k in keys}

    fn = jax.jit(jax.shard_map(local_fn, mesh=mesh,
                               in_specs=(STATE_PSPEC,),
                               out_specs=cache_specs, check_vma=False))

    def prepare(state: TMState) -> TMBundle:
        state = pad_state(cfg, state, geom.n_padded)
        state = TMState(ta_state=jax.device_put(state.ta_state, state_sh))
        caches = fn(state) if keys else {}
        return TMBundle(cfg=cfg, state=state, caches=caches,
                        event_overflow=jnp.zeros((), jnp.int32))

    return prepare


def make_sharded_scores(cfg: TMConfig, mesh, *, engine: str = DEFAULT_ENGINE):
    """``(TMBundle, x) -> (B, m)`` scores through one engine, clause-sharded.

    Exactly one collective: the psum of per-shard partial votes (GSPMD
    lowers it to a single (B, m) all-reduce over ``CLAUSE_AXIS``). The batch
    shards over the data/pod axes communication-free. Clause-axis padding
    rows contribute zero partial votes (sign-0 polarity), so the reduced
    scores are the global Eq. 3/4 values for any ``(clause_shards,
    n_clauses)`` pair.
    """
    eng = get_engine(engine)
    baxes = batch_axes(mesh)
    bspec = P(baxes, None) if baxes else P(None, None)
    cache_spec = eng.cache_pspec(cfg)
    pol = _sharded_polarity(cfg, mesh)

    def local_fn(cache_l, pol_l, x_l):
        part = eng.partial_scores(cfg, cache_l, x_l, pol_l)
        return jax.lax.psum(part, CLAUSE_AXIS)

    fn = jax.jit(jax.shard_map(
        local_fn, mesh=mesh, in_specs=(cache_spec, P(CLAUSE_AXIS), bspec),
        out_specs=bspec, check_vma=False))

    def operand(bundle: TMBundle):
        """The engine operand ``fn`` evaluates: the TA state for cache-less
        engines, the prepared shard-local cache otherwise."""
        if not eng.needs_cache:
            return bundle.state
        cache = bundle.caches.get(eng.cache_key)
        if cache is None:
            raise KeyError(
                f"engine {engine!r} (cache slot {eng.cache_key!r}) was not "
                f"prepared in this bundle (slots: {tuple(bundle.caches)}); "
                "include it in the engines= of make_sharded_prepare / the "
                "TMSession — sharded caches cannot be built on the fly")
        return cache

    def scores(bundle: TMBundle, x: jax.Array) -> jax.Array:
        return fn(operand(bundle), pol, x)

    def aot_jit(donate_x: bool = False):
        """The same shard_map body under an AOT-friendly ``jax.jit``:
        explicit per-operand in/out ``NamedSharding``s (so
        ``.lower(...).compile()`` bakes the placement into the executable
        instead of re-inferring it per call) and, when ``donate_x``, the
        batch operand donated — the serving AOT cache's lowering target
        (``TMSession.lower_scores`` / ``serving/aot.py``)."""
        as_named = lambda spec: jax.tree.map(  # noqa: E731
            lambda s: NamedSharding(mesh, s), spec,
            is_leaf=lambda s: isinstance(s, P))
        return jax.jit(
            jax.shard_map(local_fn, mesh=mesh,
                          in_specs=(cache_spec, P(CLAUSE_AXIS), bspec),
                          out_specs=bspec, check_vma=False),
            in_shardings=(as_named(cache_spec), as_named(P(CLAUSE_AXIS)),
                          as_named(bspec)),
            out_shardings=as_named(bspec),
            donate_argnums=(2,) if donate_x else ())

    # exposed for the dry-run's HLO assertions (launch/dryrun.py --tm) and
    # the AOT serving cache's lowering hook (core/session.py lower_scores)
    scores.jitted, scores.pol, scores.engine = fn, pol, eng
    scores.operand, scores.aot_jit, scores.bspec = operand, aot_jit, bspec
    return scores


def make_sharded_train_step(cfg: TMConfig, mesh, *, engines=None,
                            parallel: bool = False, max_events: int = 4096,
                            donate: bool | None = None):
    """``(TMBundle, xs, ys, rng[, mask]) -> TMBundle``, sharded end to end.

    Sequential mode keeps the paper's global sample order (online learning
    is sequential in samples by definition), so the data/pod axes cannot
    shard the *batch* — instead they compose with the clause axis
    **hierarchically**: each data rank scans the full batch over its own
    zero-padded clause *sub-slice* of ``⌈n_local/data_shards⌉`` rows
    (global clause order = model-major, data-minor), and one final psum
    over the data axes reassembles the model-shard slice. The vote psum
    inside ``tm._class_round`` then runs over *all* mesh axes — it already
    composed; the batch-order question is answered by giving the data axis
    clause work, not batch work. Padding rows (ragged sub-slices and the
    global clause-axis padding, DESIGN.md §9) carry sign-0 polarity and a
    zero update mask, so they are inert through the vote psum and frozen
    through the feedback kernels; sub-slice padding is dropped by the
    reassembly slice. Only when ``data_shards > n_local`` does the
    sequential step fall back to PR-2 batch replication (warned once,
    ``composition_rule='replicated'``). The batch-parallel approximation
    shards the batch over data/pod as before, psumming the summed TA
    deltas. Either way every collective is an all-reduce; the include-mask
    diff and every cache's event replay stay on the model shard
    (``max_events`` bounds the *per-shard* event buffer). Bit-exact with
    the single-device ``api.train_step`` (identical randomness: each shard
    draws its own rows of the same stream).

    ``mask`` (B,) bool marks valid samples (the fixed-shape padding
    contract of ``api.train_step``); omitted → all rows valid. The fired
    composition rule is exposed as ``step.composition`` (and recorded by
    ``dryrun --tm`` / BENCH_tm_serve.json).
    """
    geom = geometry(cfg, mesh)
    n_local = geom.n_local
    _, cache_specs = bundle_pspecs(cfg, engines)
    all_baxes = batch_axes(mesh)
    d_shards = geom.data_shards
    # sequential: hierarchical data×clause composition (even or ragged)
    compose = (not parallel) and geom.composes
    if (not parallel) and geom.composition == REPLICATED:
        warnings.warn(
            f"sequential sharded training fired composition rule "
            f"'{REPLICATED}': data_shards={d_shards} exceeds the per-shard "
            f"clause count n_local={n_local} (n_clauses={cfg.n_clauses} / "
            f"clause_shards={geom.clause_shards}), so there is no clause "
            "sub-slice to hand each data rank — the data axis replicates "
            "the batch instead of adding clause parallelism. Pick "
            "data_shards <= n_local to compose (rules "
            f"'{COMPOSED_EVEN}'/'{COMPOSED_RAGGED}', DESIGN.md §9).",
            RuntimeWarning, stacklevel=2)
    n_sub = geom.n_sub if compose else n_local
    n_sub_pad = geom.n_sub_padded if compose else n_local
    baxes = all_baxes if parallel else ()
    x_spec = P(baxes, None) if baxes else P(None, None)
    y_spec = P(baxes) if baxes else P(None)
    pol = _sharded_polarity(cfg, mesh)

    def shard_step(state_l: TMState, caches_l, pol_l, xs, ys, key_data,
                   mask):
        # The shard-local step under the step's scope names
        # (core/scopes.py): the shard's Type I/II update, then the
        # events-and-sync tail of api.train_step. Returns
        # (new_state, new_caches, EventBuffer).
        rng = jax.random.wrap_key_data(key_data)
        start = jax.lax.axis_index(CLAUSE_AXIS) * n_local
        with jax.named_scope(scopes.EVENTS):
            old_inc = include_mask(cfg, state_l)
        # validity of this shard's local rows: only the trailing shard(s)
        # carry global clause-axis padding; None when the layout is exact
        # (keeps the even-geometry HLO identical to the pre-ragged path)
        local_valid = None
        if geom.ragged_clauses:
            local_valid = (start + jnp.arange(n_local)) < cfg.n_clauses
        with jax.named_scope(scopes.FEEDBACK):
            if parallel:
                b_idx = jnp.int32(0)
                for a in baxes:
                    b_idx = b_idx * mesh.shape[a] + jax.lax.axis_index(a)
                b_total = (xs.shape[0]
                           * math.prod(mesh.shape[a] for a in baxes)
                           if baxes else None)
                new_state = tm.update_batch_parallel(
                    cfg, state_l, xs, ys, rng, pol=pol_l,
                    axis_name=CLAUSE_AXIS, clause_start=start,
                    batch_axes=baxes, batch_start=b_idx * xs.shape[0],
                    batch_total=b_total, mask=mask, clause_mask=local_valid)
            elif compose:
                # this data rank owns clause rows [d·n_sub, (d+1)·n_sub) of
                # the model shard's (sub-slice-padded) slice; votes psum
                # over (data axes + clause axis)
                d_idx = jnp.int32(0)
                for a in all_baxes:
                    d_idx = d_idx * mesh.shape[a] + jax.lax.axis_index(a)
                off = d_idx * n_sub
                ta_pad = _pad_rows(state_l.ta_state, 1, n_sub_pad,
                                   cfg.n_states)
                pol_pad = _pad_rows(pol_l, 0, n_sub_pad, 0)
                sub = TMState(ta_state=jax.lax.dynamic_slice_in_dim(
                    ta_pad, off, n_sub, 1))
                pol_sub = jax.lax.dynamic_slice_in_dim(pol_pad, off, n_sub, 0)
                sub_valid = None
                if geom.composition == COMPOSED_RAGGED or geom.ragged_clauses:
                    rows = off + jnp.arange(n_sub)
                    sub_valid = ((rows < n_local)
                                 & ((start + rows) < cfg.n_clauses))
                new_state = tm.update_batch_sequential(
                    cfg, sub, xs, ys, rng, pol=pol_sub,
                    axis_name=(*all_baxes, CLAUSE_AXIS),
                    clause_start=start + off, mask=mask,
                    clause_mask=sub_valid)
            else:
                new_state = tm.update_batch_sequential(
                    cfg, state_l, xs, ys, rng, pol=pol_l,
                    axis_name=CLAUSE_AXIS, clause_start=start, mask=mask,
                    clause_mask=local_valid)
            if compose:
                # reassemble the model shard's slice: each real row is owned
                # by exactly one data rank, so a zero-padded psum is a
                # gather expressed as the one collective kind this step
                # allows; the trailing sub-slice padding rows land past
                # n_local and are dropped by the slice
                zeros = jnp.zeros(
                    (state_l.ta_state.shape[0], n_sub_pad,
                     state_l.ta_state.shape[2]), state_l.ta_state.dtype)
                assembled = jax.lax.dynamic_update_slice_in_dim(
                    zeros, new_state.ta_state, off, 1)
                summed = jax.lax.psum(assembled, all_baxes)
                new_state = TMState(ta_state=jax.lax.slice_in_dim(
                    summed, 0, n_local, axis=1))
        new_caches, buf = replay_events(cfg, caches_l, old_inc, new_state,
                                        max_events)
        return new_state, new_caches, buf

    def local_fn(state_l: TMState, caches_l, pol_l, xs, ys, key_data, mask,
                 overflow_in):
        new_state, new_caches, buf = shard_step(
            state_l, caches_l, pol_l, xs, ys, key_data, mask)
        # per-shard drop counts add over the clause axis (each model shard
        # diffs only its own include slice; data ranks see identical diffs),
        # yielding the replicated global overflow counter — an all-reduce,
        # never a gather, per the step's collective contract
        overflow = overflow_in + jax.lax.psum(buf.overflow, CLAUSE_AXIS)
        return new_state, new_caches, overflow

    mask_spec = y_spec  # batch-sharded in parallel mode, replicated otherwise
    sm = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(STATE_PSPEC, cache_specs, P(CLAUSE_AXIS), x_spec,
                  y_spec, P(None), mask_spec, P()),
        out_specs=(STATE_PSPEC, cache_specs, P()), check_vma=False)
    donate_nums = (0, 1) if resolve_donate(donate) else ()
    fn = jax.jit(sm, donate_argnums=donate_nums)

    def step(bundle: TMBundle, xs, ys, rng, mask=None) -> TMBundle:
        if mask is None:
            mask = jnp.ones(xs.shape[0], bool)
        overflow_in = (bundle.event_overflow
                       if bundle.event_overflow is not None
                       else jnp.zeros((), jnp.int32))
        new_state, new_caches, overflow = fn(
            bundle.state, bundle.caches, pol, xs, ys,
            jax.random.key_data(rng), mask, overflow_in)
        return TMBundle(cfg=cfg, state=new_state, caches=new_caches,
                        event_overflow=overflow)

    # exposed for the dry-run's HLO assertions (launch/dryrun.py --tm)
    step.jitted, step.pol = fn, pol
    step.geometry = geom
    step.composition = "batch_parallel" if parallel else geom.composition
    return step


# The stateful facade over these factories is ``core/session.py``'s
# ``TMSession`` (``ShardedTM`` in PR 2): one session resolves a ``Topology``
# into either this shard_map path or the single-device jitted path, so
# callers never wire prepare/scores/train_step by hand.
