"""The plain reference the benchmark holds the program to.

Straight ``jax.numpy`` from the paper (arXiv 2004.03188 §2, Eq. 1-3 and the
Type I / Type II feedback tables), written without importing anything of
the program under test:

* ``scores`` — Eq. 3 class scores of a TA state: a clause is true iff none
  of its included literals is false (an empty clause counts as true, the
  paper's Eq. 4 convention), and a class scores its true positive clauses
  minus its true negative ones. Float32 falsification counts at
  ``HIGHEST`` precision (0/1 operands: exact), integer votes.
* ``train_steps`` — sequential online learning over batches: per sample a
  positive round on the target class and a negative round on one other
  class drawn uniformly, each with Type I / Type II feedback.
* ``pack_include`` — the include mask in 32-bit words, literal k at bit
  k % 32 of word k // 32.

A stochastic learner can only be compared exactly when both sides use the
same draws, so ``train_steps`` consumes uniforms drawn from the step key by
the TM's documented protocol: per-sample keys ``split(key, B)``; per sample
``split(k, 3)`` into the negative-class draw and the two rounds; per round
``split(k_round)`` into the clause gate ``(n,)`` and the Type I uniforms
``(n, 2o)``.

``state_dtype`` and ``uniform_dtype`` exist for the controls only: the
reference run one precision below what the configuration states
(``control.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

WORD = 32


def _literals(x):
    x = x.astype(jnp.int32)
    return jnp.concatenate([x, 1 - x], axis=-1)


def _include(ta, n_states, state_dtype):
    return ta.astype(state_dtype) > n_states


@functools.partial(jax.jit, static_argnames=("n_states", "state_dtype"))
def scores(ta, x, *, n_states: int, state_dtype=jnp.int16):
    """(m, n, 2o) TA states, (B, o) 0/1 inputs → (B, m) int32 scores."""
    m, n, _ = ta.shape
    include = _include(ta, n_states, state_dtype).astype(jnp.float32)
    false_lit = (1 - _literals(x)).astype(jnp.float32)
    falsified = jnp.einsum("bk,mnk->bmn", false_lit, include,
                           precision=jax.lax.Precision.HIGHEST)
    true = (falsified < 0.5).astype(jnp.int32)
    pol = jnp.where(jnp.arange(n) < n // 2, 1, -1).astype(jnp.int32)
    return jnp.sum(true * pol, axis=-1)


def scores_blocked(ta, x, *, n_states: int, block: int = 256):
    """``scores`` over the rows of ``x`` in blocks (bounded temporaries)."""
    out = []
    for i in range(0, x.shape[0], block):
        xb = x[i:i + block]
        pad = block - xb.shape[0]
        if pad:
            xb = np.concatenate([xb, np.zeros((pad,) + xb.shape[1:],
                                              xb.dtype)])
        s = scores(ta, jnp.asarray(xb), n_states=n_states)
        out.append(np.asarray(s)[:block - pad])
    return np.concatenate(out)


def _round(ta_row, lit, key, positive, *, n_states, s, threshold,
           boost_true_positive, uniform_dtype):
    """One class round of feedback on ``ta_row`` (n, 2o)."""
    n, width = ta_row.shape
    k_gate, k_u = jax.random.split(key)
    gate = jax.random.uniform(k_gate, (n,))
    u = jax.random.uniform(k_u, (n, width)).astype(uniform_dtype)
    include = ta_row > n_states
    lit_true = lit == 1
    clause = ~jnp.any(include & ~lit_true[None, :], axis=-1)   # empty → True
    pol = jnp.where(jnp.arange(n) < n // 2, 1, -1)
    t = float(threshold)
    votes = jnp.clip(jnp.sum(clause.astype(jnp.int32) * pol), -t, t)
    p = jnp.where(positive, (t - votes) / (2 * t), (t + votes) / (2 * t))
    active = gate < p
    type_i = jnp.where(positive, pol > 0, pol < 0)
    inv_s = 1.0 / s
    p_reward = 1.0 if boost_true_positive else 1.0 - inv_s
    c1 = clause[:, None]
    l1 = lit_true[None, :]
    reward = c1 & l1 & (u < p_reward)
    penalty = ~(c1 & l1) & (u < inv_s)
    d_i = reward.astype(jnp.int32) - penalty.astype(jnp.int32)
    d_ii = (c1 & ~l1 & ~include).astype(jnp.int32)
    delta = jnp.where(active[:, None],
                      jnp.where(type_i[:, None], d_i, d_ii), 0)
    new = jnp.clip(ta_row.astype(jnp.int32) + delta, 1, 2 * n_states)
    return new.astype(ta_row.dtype)


@functools.partial(jax.jit, static_argnames=(
    "n_states", "s", "threshold", "boost_true_positive", "state_dtype",
    "uniform_dtype"))
def train_step(ta, xs, ys, key, *, n_states: int, s: float, threshold: int,
               boost_true_positive: bool = False, state_dtype=jnp.int16,
               uniform_dtype=jnp.float32):
    """One sequential learning step over a batch → new (m, n, 2o) states."""
    m = ta.shape[0]
    kw = dict(n_states=n_states, s=s, threshold=threshold,
              boost_true_positive=boost_true_positive,
              uniform_dtype=uniform_dtype)

    def sample(ta, inp):
        x, y, k = inp
        k_neg, k_pos_round, k_neg_round = jax.random.split(k, 3)
        neg = jax.random.randint(k_neg, (), 0, m - 1)
        neg = jnp.where(neg >= y, neg + 1, neg)
        lit = _literals(x)
        ta = ta.at[y].set(_round(ta[y], lit, k_pos_round, True, **kw))
        ta = ta.at[neg].set(_round(ta[neg], lit, k_neg_round, False, **kw))
        return ta, None

    keys = jax.random.split(key, xs.shape[0])
    out, _ = jax.lax.scan(sample, ta.astype(state_dtype), (xs, ys, keys))
    return out


def train_steps(ta, batches, keys, tm: dict):
    """States after each of ``len(batches)`` steps from ``ta``: a list of
    (m, n, 2o) arrays in the configuration's state dtype."""
    out = []
    for (xs, ys), key in zip(batches, keys):
        ta = train_step(ta, jnp.asarray(xs), jnp.asarray(ys), key,
                        n_states=tm["n_states"], s=float(tm["s"]),
                        threshold=int(tm["threshold"]),
                        boost_true_positive=tm["boost_true_positive"]
                        ).astype(jnp.int16)
        out.append(ta)
    return out


@functools.partial(jax.jit, static_argnames=("n_states",))
def pack_include(ta, *, n_states: int):
    """(m, n, 2o) states → (m, n, ceil(2o/32)) uint32 include words."""
    width = ta.shape[-1]
    words = -(-width // WORD)
    bits = (ta > n_states).astype(jnp.uint32)
    bits = jnp.pad(bits, [(0, 0), (0, 0), (0, words * WORD - width)])
    bits = bits.reshape(ta.shape[:-1] + (words, WORD))
    return jnp.sum(bits << jnp.arange(WORD, dtype=jnp.uint32), axis=-1,
                   dtype=jnp.uint32)
