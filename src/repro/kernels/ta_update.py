"""Pallas TPU kernel: Type I / Type II TA feedback application.

Second hot spot of TM *learning*: given precomputed clause outputs and the
per-clause feedback routing (active gate, Type I vs Type II), apply the
per-(clause, literal) state transitions. Elementwise over (n, 2o) with two
broadcast operands — a pure VPU kernel; tiling keeps the uniforms and TA
block resident in VMEM so the update is one HBM read + one write of the
TA state per step.

Layout: clauses on sublanes (CLAUSE_TILE), literals on lanes (the whole
literal axis when 2o ≤ LIT_TILE, else LIT_TILE-wide tiles). The three
per-clause gates travel as one int32 feedback code per clause and the
literals as int32, because Mosaic has no int8 vector comparison on the
TPU; the TA state is read and written as int16 and updated in int32.
Trailing tiles may be partial: their out-of-range entries only reach
out-of-range outputs, whose writes are dropped — no operand is padded.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

CLAUSE_TILE = 32     # int16 state tiles are (16, 128): a multiple of it
LIT_TILE = 2048      # max literal columns per block (multiple of 128)

# feedback-code bits, one int32 per clause
_CLAUSE_TRUE, _TYPE_I, _ACTIVE = 1, 2, 4


def _update_kernel(
    ta_ref,        # (Ct, Lt) int16
    lit_ref,       # (1, Lt) int32   — literal truth values
    code_ref,      # (Ct, 1) int32   — feedback code bits (see _CLAUSE_TRUE…)
    u_ref,         # (Ct, Lt) float32 — uniforms for Type I branches
    o_ref,         # (Ct, Lt) int16
    *,
    n_states: int,
    s: float,
    boost_true_positive: bool,
):
    ta = ta_ref[...].astype(jnp.int32)
    l1 = lit_ref[...] == 1                        # (1, Lt)
    code = code_ref[...]                          # (Ct, 1)
    c1 = (code & _CLAUSE_TRUE) != 0
    is_t1 = (code & _TYPE_I) != 0
    active = (code & _ACTIVE) != 0
    u = u_ref[...]
    include = ta > n_states

    inv_s = 1.0 / s
    p_reward = 1.0 if boost_true_positive else 1.0 - inv_s

    # Type I deltas
    reward = c1 & l1 & (u < p_reward)
    penalty = ((c1 & ~l1) | ~c1) & (u < inv_s)
    d1 = reward.astype(jnp.int32) - penalty.astype(jnp.int32)
    # Type II deltas
    d2 = (c1 & ~l1 & ~include).astype(jnp.int32)

    delta = jnp.where(active & is_t1, d1, jnp.where(active & ~is_t1, d2, 0))
    o_ref[...] = jnp.clip(ta + delta, 1, 2 * n_states).astype(jnp.int16)


@functools.partial(
    jax.jit, static_argnames=("n_states", "s", "boost_true_positive", "interpret")
)
def ta_update(
    ta_row: jax.Array,       # (n, 2o) int16 — one class's TA states
    lit: jax.Array,          # (2o,) int8/uint8
    clause_out: jax.Array,   # (n,) int8
    gets_type_i: jax.Array,  # (n,) bool/int8
    active: jax.Array,       # (n,) bool/int8
    uniforms: jax.Array,     # (n, 2o) float32
    *,
    n_states: int,
    s: float,
    boost_true_positive: bool = False,
    interpret: bool = True,
) -> jax.Array:
    """Apply one class-round of feedback. Returns updated (n, 2o) int16."""
    n, L = ta_row.shape
    ct, lt = min(n, CLAUSE_TILE), min(L, LIT_TILE)
    code = ((clause_out == 1).astype(jnp.int32) * _CLAUSE_TRUE
            + gets_type_i.astype(bool).astype(jnp.int32) * _TYPE_I
            + active.astype(bool).astype(jnp.int32) * _ACTIVE)

    grid = (pl.cdiv(n, ct), pl.cdiv(L, lt))
    return pl.pallas_call(
        functools.partial(
            _update_kernel,
            n_states=n_states,
            s=s,
            boost_true_positive=boost_true_positive,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ct, lt), lambda i, j: (i, j)),
            pl.BlockSpec((1, lt), lambda i, j: (0, j)),
            pl.BlockSpec((ct, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((ct, lt), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((ct, lt), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, L), jnp.int16),
        interpret=interpret,
    )(ta_row.astype(jnp.int16), lit.astype(jnp.int32)[None, :],
      code[:, None], uniforms)
