"""The TM Pallas kernels compile for a TPU v5e at the paper's widths.

Interpret mode (every other kernel test) cannot see the chip compiler's
rules: block tiling, vector dtypes Mosaic supports, VMEM limits. Here each
kernel is compiled with ``interpret=False`` for one chip of a *described*
``v5e:2x2`` topology — nothing runs, so no TPU is needed — at M1 width
(``configs/tm.py`` ``mnist_like(1)``: 10 classes, 2000 clauses, 1568
literals) and, for ``indexed_votes``, also at I1 width (2o = 10,000).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU compiler library, and every
test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.tm import imdb_like, mnist_like
from repro.kernels import clause_eval, indexed, ta_update

M1 = mnist_like(1).tm
I1 = imdb_like(5000).tm
SERVE_BATCH = 32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _kernel_case(name, cfg):
    """(callable, ((shape, dtype), ...)) for one kernel at ``cfg``'s width;
    the serving kernels at a 32-row batch, the training ones as the learning
    round calls them (one class row, one sample)."""
    m, n, L = cfg.n_classes, cfg.n_clauses, cfg.n_literals
    w = -(-L // 32)
    if name == "clause_votes":
        return (lambda a, b, c: clause_eval.clause_votes_packed(
                    a, b, c, interpret=False),
                (((m, n, w), jnp.uint32), ((SERVE_BATCH, w), jnp.uint32),
                 ((n,), jnp.int32)))
    if name == "clause_outputs":
        return (lambda a, b: clause_eval.clause_outputs_packed(
                    a, b, interpret=False),
                (((1, n, w), jnp.uint32), ((1, w), jnp.uint32)))
    if name == "indexed_votes":
        return (lambda a, b, c: indexed.indexed_votes(
                    a, b, c, interpret=False),
                (((m, n, L), jnp.int32), ((SERVE_BATCH, L), jnp.uint8),
                 ((n,), jnp.int32)))
    if name == "ta_update":
        return (lambda *a: ta_update.ta_update(
                    *a, n_states=cfg.n_states, s=cfg.s, interpret=False),
                (((n, L), jnp.int16), ((L,), jnp.uint8), ((n,), jnp.int8),
                 ((n,), jnp.bool_), ((n,), jnp.bool_),
                 ((n, L), jnp.float32)))
    raise NotImplementedError(name)


@pytest.mark.parametrize("name,width", [
    ("clause_votes", "M1"),
    ("clause_outputs", "M1"),
    ("indexed_votes", "M1"),
    ("ta_update", "M1"),
    ("indexed_votes", "I1"),
])
def test_kernel_compiles_for_v5e(one_chip, name, width):
    cfg = {"M1": M1, "I1": I1}[width]
    fn, shapes = _kernel_case(name, cfg)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
