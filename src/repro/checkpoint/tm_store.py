"""Versioned, engine-agnostic TM checkpoints (schema v1) over ``Checkpointer``.

Replaces the legacy driver pytree schema (``as_pytree``/``load_pytree``),
which persisted the falsification index alongside the TA state. Schema v1
persists **state + config fingerprint only**:

  * every engine cache — including the paper's clause index — is derived
    data; persisting one would pin the topology it was built on (shard-local
    cache layouts change shape with the clause-shard count). Restore rebuilds
    caches on the *restoring* topology via ``TMSession.prepare`` — the same
    reshard-on-restore machinery the fault-tolerant trainer uses — so a
    checkpoint written under ``Topology(clause_shards=4)`` loads bit-exactly
    under any other placement;
  * the config fingerprint (sha256 over the canonical ``TMConfig`` field
    dump) catches restoring into a machine whose semantics differ — shapes
    alone cannot (e.g. a changed ``s`` or ``threshold`` keeps every shape).

On disk this is a normal ``Checkpointer`` step directory (atomic commit,
retention, async save), holding ``schema_version``, ``fingerprint``,
``step`` and ``ta_state`` arrays. The fingerprint is validated *before* the
state is read, so a config mismatch fails with a clear error rather than a
shape complaint.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from pathlib import Path

import numpy as np

from repro.checkpoint.checkpointer import Checkpointer

SCHEMA_VERSION = 1
_DIGEST_BYTES = 32  # sha256


class CheckpointMismatch(ValueError):
    """Checkpoint incompatible with the restoring machine's config/schema."""


# Execution details that do not change what a checkpoint *is*: a state
# trained on one kernel backend restores onto any other (results are
# bit-exact across backends by the registry contract), exactly like
# restoring onto a different topology.
_EXECUTION_FIELDS = frozenset({"backend"})


def config_fingerprint(cfg) -> np.ndarray:
    """(32,) uint8 sha256 over the canonical config field dump.

    Every *model* dataclass field participates (capacities included: they
    size the rebuilt caches); pure execution fields (``_EXECUTION_FIELDS``)
    do not. Values render via ``repr`` for a stable text form that also
    covers non-JSON leaves like dtypes.
    """
    fields = {f.name: repr(getattr(cfg, f.name))
              for f in dataclasses.fields(cfg)
              if f.name not in _EXECUTION_FIELDS}
    blob = json.dumps(fields, sort_keys=True).encode()
    return np.frombuffer(hashlib.sha256(blob).digest(), np.uint8).copy()


def checkpoint_tree(cfg, ta_state, *, step: int = 0) -> dict:
    """The schema-v1 payload for one TM state (a flat dict pytree)."""
    return {
        "schema_version": np.asarray(SCHEMA_VERSION, np.int32),
        "fingerprint": config_fingerprint(cfg),
        "step": np.asarray(step, np.int32),
        "ta_state": ta_state,
    }


def validate_meta(loaded: dict, cfg, *, where: str = "checkpoint") -> None:
    """Raise ``CheckpointMismatch`` on a schema or fingerprint mismatch."""
    version = int(np.asarray(loaded["schema_version"]))
    if version != SCHEMA_VERSION:
        raise CheckpointMismatch(
            f"{where}: schema version {version} != supported "
            f"{SCHEMA_VERSION}")
    want = config_fingerprint(cfg)
    got = np.asarray(loaded["fingerprint"], np.uint8)
    if got.shape != want.shape or not np.array_equal(got, want):
        raise CheckpointMismatch(
            f"{where}: config fingerprint mismatch — the checkpoint was "
            f"written with a different TMConfig than the restoring "
            f"machine's (saved {bytes(got[:8]).hex()}…, restoring "
            f"{bytes(want[:8]).hex()}…); load with the original config")


# One Checkpointer per directory: its save() serialises in-flight writes
# (one at a time) and surfaces a failed async write on the *next* call — a
# throwaway instance per save would silently swallow non-blocking errors
# and race concurrent writer threads over the same directory.
_CHECKPOINTERS: dict[str, Checkpointer] = {}


def _checkpointer(directory, keep: int | None = None) -> Checkpointer:
    key = str(Path(directory).resolve())
    ck = _CHECKPOINTERS.get(key)
    if ck is None:
        ck = Checkpointer(directory, keep=3 if keep is None else keep)
        _CHECKPOINTERS[key] = ck
    elif keep is not None:
        ck.keep = keep
    return ck


def save_tm(directory, cfg, ta_state, *, step: int = 0, keep: int = 3,
            blocking: bool = True) -> None:
    """Write one schema-v1 checkpoint step (atomic, retained per ``keep``)."""
    _checkpointer(directory, keep=keep).save(
        step, checkpoint_tree(cfg, ta_state, step=step), blocking=blocking)


def load_tm(directory, cfg, like_ta_state, *, step: int | None = None,
            sharding=None):
    """Restore ``(ta_state, step)`` from the newest (or given) step.

    ``like_ta_state`` supplies the target shape/dtype (any array or
    ShapeDtypeStruct-alike with ``.shape``); ``sharding`` (optional
    ``jax.sharding.Sharding``) lands the state directly on the restoring
    topology's placement — reshard-on-restore. Meta is validated *first* so
    config mismatches surface as ``CheckpointMismatch``, never as a shape
    error from the state read.
    """
    ckpt = _checkpointer(directory)
    ckpt.wait()  # drain any in-flight save (and surface its error) first
    if step is None:
        step = ckpt.latest_step()
        if step is None:
            raise FileNotFoundError(
                f"no committed TM checkpoint steps under {directory}")
    try:
        meta = ckpt.restore(step, {
            "schema_version": np.asarray(0, np.int32),
            "fingerprint": np.zeros(_DIGEST_BYTES, np.uint8)})
    except KeyError as e:  # pre-v1 layouts carry no schema/fingerprint
        raise CheckpointMismatch(
            f"{directory} step {step}: not a schema-v1 TM checkpoint "
            f"(missing {e}); pre-versioning checkpoints (the legacy driver "
            "pytree) are not loadable — re-save from the source state"
        ) from None
    validate_meta(meta, cfg, where=f"{directory} step {step}")
    shardings = ({"ta_state": sharding} if sharding is not None else None)
    loaded = ckpt.restore(step, {"ta_state": like_ta_state}, shardings)
    return loaded["ta_state"], step


__all__ = [
    "SCHEMA_VERSION", "CheckpointMismatch", "checkpoint_tree",
    "config_fingerprint", "load_tm", "save_tm", "validate_meta",
]
