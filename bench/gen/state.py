"""A TA state on the device, made from the seed in one jitted call.

It stands in for a machine part-way through training (listed under
``assumed`` in each configuration file):

* every clause is drawn from one class prototype (``gen/data.py``): a
  positive clause of class i from class i's, a negative clause of class i
  from a uniformly drawn other class — as a trained TM's clauses are
  sub-patterns of the data they vote for or against;
* a clause includes each literal that is true in its prototype with
  probability ``f · avg_len / o``, where ``f`` is drawn per clause,
  uniform on [0, 2): clause lengths then spread around the paper's average
  clause length ``avg_len`` (§3: 58 for MNIST, 116 for IMDb), and no
  literal that is false in the prototype is included;
* included depths are uniform on [N+1, 2N], excluded depths on [1, N], so
  one step of feedback flips only the TAs next to the boundary.

A state drawn with no regard to the data (each TA included with
probability avg_len/2o) falsifies every clause on every input at these
lengths, so every score would be 0 and a broken scorer could not be told
from a sound one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.gen.data import rng_for


def jax_key(seed: int, stream: int) -> jax.Array:
    """A JAX key for one use of a run's seed (any size of seed)."""
    return jax.random.key(int(rng_for(seed, stream).integers(0, 2**31 - 1)))


@functools.partial(jax.jit, static_argnames=("n_clauses", "n_states",
                                              "avg_len", "dtype"))
def ta_state(key, proto, *, n_clauses: int, n_states: int, avg_len: float,
             dtype=jnp.int16):
    """(m, n, 2o) TA states from class prototypes ``proto`` (m, o) 0/1."""
    m, o = proto.shape
    half = n_clauses // 2
    k_cls, k_len, k_inc, k_in, k_out = jax.random.split(key, 5)
    lit_proto = jnp.concatenate([proto, 1 - proto], axis=-1).astype(bool)
    other = (jnp.arange(m)[:, None]
             + 1 + jax.random.randint(k_cls, (m, n_clauses), 0, max(m - 1, 1))
             ) % m
    own = jnp.broadcast_to(jnp.arange(m)[:, None], (m, n_clauses))
    cls = jnp.where(jnp.arange(n_clauses)[None, :] < half, own, other)
    p = (jax.random.uniform(k_len, (m, n_clauses, 1), maxval=2.0)
         * (avg_len / o))
    include = lit_proto[cls] & (
        jax.random.uniform(k_inc, (m, n_clauses, 2 * o)) < p)
    shape = (m, n_clauses, 2 * o)
    depth_in = jax.random.randint(k_in, shape, n_states + 1, 2 * n_states + 1)
    depth_out = jax.random.randint(k_out, shape, 1, n_states + 1)
    return jnp.where(include, depth_in, depth_out).astype(dtype)


def make_state(tm: dict, proto: np.ndarray, avg_len: float, seed: int):
    """The cell's initial TA state on the default device."""
    return ta_state(jax_key(seed, 3), jnp.asarray(proto),
                    n_clauses=tm["n_clauses"], n_states=tm["n_states"],
                    avg_len=float(avg_len))
