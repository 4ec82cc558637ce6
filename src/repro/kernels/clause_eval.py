"""Pallas TPU kernels: bit-packed clause evaluation (+ fused voting).

The paper's dense hot spot is evaluating m·n conjunctive clauses over 2o
literals. TPU-native layout (DESIGN.md §2):

  * literals bit-packed 32/uint32 word → operand bytes drop 32×;
  * clauses on sublanes (tiles of CLAUSE_TILE), packed words on lanes (the
    block spans all W words, so the include cache is never padded);
  * falsification is `any(include & ~literals)` — one VPU pass, no MXU;
  * the vote reduction is fused so (B, m, n) clause outputs never
    round-trip through HBM: the kernel emits votes directly, into a
    lane-dense (BATCH_TILE, m_pad) block where class i owns lane i.

Blocks obey the TPU tiling rule (last two block dims divisible by 8 and 128
or equal to the array's): a trailing clause tile may be partial — its
out-of-range rows read unspecified words, but their polarity is padded
with 0 (votes) or their outputs fall outside the array (dropped writes).

VMEM budget per grid step: include block CLAUSE_TILE×W×4B + literal block
BATCH_TILE×W×4B + the (BATCH_TILE, CLAUSE_TILE, W) violation temporary;
W = 1250 at 2o = 40k literals → ≈ 6 MB, inside the 16 MB scoped default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BATCH_TILE = 8       # sublane-friendly batch tile
CLAUSE_TILE = 128    # clauses per grid step
LANE = 128           # lane width; the vote block's class axis pads to it


def pad_to(x: jax.Array, axis: int, mult: int, value=0) -> jax.Array:
    """Pad ``axis`` of ``x`` up to a multiple of ``mult`` with ``value``."""
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def accumulate_class_votes(o_ref, cls, votes) -> None:
    """Add ``votes`` (BATCH_TILE, 1) into lane ``cls`` of the lane-dense
    (BATCH_TILE, m_pad) vote block — a full-tile masked add, no
    dynamic-lane store."""
    lane = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)
    o_ref[...] += jnp.where(lane == cls, votes, 0)


def _falsified(inc_ref, lit_ref) -> jax.Array:
    """(BATCH_TILE, CLAUSE_TILE) bool: some included literal is false."""
    inc = inc_ref[0]                                    # (Ct, W)
    lit = lit_ref[...]                                  # (Bt, W)
    viol = inc[None, :, :] & (~lit)[:, None, :]         # (Bt, Ct, W)
    return jnp.any(viol != 0, axis=-1)


# ---------------------------------------------------------------------------
# Fused eval + vote kernel
# ---------------------------------------------------------------------------


def _votes_kernel(inc_ref, lit_ref, pol_ref, o_ref):
    """Grid (B_tiles, m, n_tiles); j = clause-tile index iterates fastest.

    inc_ref: (1, CLAUSE_TILE, W)   uint32 — include masks of clause tile
    lit_ref: (BATCH_TILE, W)       uint32 — packed literals
    pol_ref: (1, CLAUSE_TILE)      int32  — ±1 clause polarity (0 = padding)
    o_ref:   (BATCH_TILE, m_pad)   int32  — votes, resident over (i, j)
    """
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when((i == 0) & (j == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    falsified = _falsified(inc_ref, lit_ref)            # (Bt, Ct)
    # polarity arrives as data (not recomputed from the global clause id),
    # so a clause shard passes its local ±1 slice and the kernel is
    # placement-agnostic; clause padding carries sign 0
    sign = pol_ref[...]                                 # (1, Ct)
    votes = jnp.sum(jnp.where(falsified, 0, sign), axis=1, keepdims=True)
    accumulate_class_votes(o_ref, i, votes)


@functools.partial(jax.jit, static_argnames=("interpret",))
def clause_votes_packed(
    include_packed: jax.Array,  # (m, n, W) uint32
    lit_packed: jax.Array,      # (B, W) uint32
    pol: jax.Array,             # (n,) int32 ±1 clause polarity
    *,
    interpret: bool = True,
) -> jax.Array:
    """Fused bit-packed clause evaluation + polarity vote: (B, m) int32.

    ``pol`` is the ±1 vote sign per clause *row of this tensor* — the global
    polarity single-device, the shard's local slice under shard_map (where
    the returned votes are partial sums completed by one psum over the
    clause axis — the registry's ``clause_votes`` partitioning contract).

    Padding invariants: include words beyond 2o are 0 (never falsify);
    literal words beyond 2o may be anything (ANDed against 0 includes);
    clause rows beyond n get sign 0.
    """
    m, n, w = include_packed.shape
    b = lit_packed.shape[0]

    lit = pad_to(lit_packed, 0, BATCH_TILE)
    ct = min(n, CLAUSE_TILE)
    polp = pad_to(pol.astype(jnp.int32)[None, :], 1, ct)
    b_pad, m_pad = lit.shape[0], pl.cdiv(m, LANE) * LANE

    grid = (b_pad // BATCH_TILE, m, pl.cdiv(n, ct))
    out = pl.pallas_call(
        _votes_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, ct, w), lambda bb, i, j: (i, j, 0)),
            pl.BlockSpec((BATCH_TILE, w), lambda bb, i, j: (bb, 0)),
            pl.BlockSpec((1, ct), lambda bb, i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((BATCH_TILE, m_pad), lambda bb, i, j: (bb, 0)),
        out_shape=jax.ShapeDtypeStruct((b_pad, m_pad), jnp.int32),
        interpret=interpret,
    )(include_packed, lit, polp)
    return out[:b, :m]


# ---------------------------------------------------------------------------
# Raw clause-output kernel (training needs per-clause outputs)
# ---------------------------------------------------------------------------


def _outputs_kernel(inc_ref, lit_ref, o_ref):
    """Grid (B_tiles, m, n_tiles): emit one (BATCH_TILE, CLAUSE_TILE) tile
    of class i's clause outputs (int32: Mosaic stores it lane-dense)."""
    falsified = _falsified(inc_ref, lit_ref)            # (Bt, Ct)
    o_ref[0] = jnp.where(falsified, 0, 1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def clause_outputs_packed(
    include_packed: jax.Array,  # (m, n, W) uint32
    lit_packed: jax.Array,      # (B, W) uint32
    *,
    interpret: bool = True,
) -> jax.Array:
    """Bit-packed clause outputs: (B, m, n) int8 (empty clauses → 1).

    The kernel writes a class-major (m, B_pad, n) int32 array so every
    output block is a (BATCH_TILE, CLAUSE_TILE) tile; the (B, m, n) int8
    view is one cheap relayout outside.
    """
    m, n, w = include_packed.shape
    b = lit_packed.shape[0]

    lit = pad_to(lit_packed, 0, BATCH_TILE)
    b_pad, ct = lit.shape[0], min(n, CLAUSE_TILE)

    grid = (b_pad // BATCH_TILE, m, pl.cdiv(n, ct))
    out = pl.pallas_call(
        _outputs_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, ct, w), lambda bb, i, j: (i, j, 0)),
            pl.BlockSpec((BATCH_TILE, w), lambda bb, i, j: (bb, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, BATCH_TILE, ct), lambda bb, i, j: (i, bb, j)),
        out_shape=jax.ShapeDtypeStruct((m, b_pad, n), jnp.int32),
        interpret=interpret,
    )(include_packed, lit)
    return jnp.transpose(out[:, :b], (1, 0, 2)).astype(jnp.int8)
