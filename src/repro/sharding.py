"""Sharding policy: PartitionSpecs for params and activations.

Design (DESIGN.md §4): 2-D param sharding — every weight matrix has one dim
on ``model`` (TP) and one on ``data`` (FSDP); ``pod`` is pure DP. Activations:
batch on ("pod","data"); the residual stream is additionally sequence-sharded
on ``model`` between blocks (Megatron-SP) via ``with_sharding_constraint``.

Models are written sharding-agnostic and call ``policy.act(x, kind)`` /
take param specs from ``param_specs``. ``Policy.none()`` turns every
constraint into identity (CPU unit tests).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

# Mesh axis names (single pod: data/model; multi-pod adds a pure-DP "pod").
POD, DATA, MODEL = "pod", "data", "model"


@dataclasses.dataclass(frozen=True)
class Policy:
    """Activation/param sharding policy bound to mesh axis names."""

    active: bool = True
    batch_axes: tuple = (DATA,)          # axes sharding the batch dim
    model_axis: str | None = MODEL
    seq_shard_residual: bool = True      # Megatron-SP on the residual stream
    # decode_mode: weight-stationary serving. Activations' d_model dim is
    # sharded over `data`, so every weight matmul contracts a sharded dim →
    # partial dot + psum of ACTIVATION-sized tensors (KBs). Without it,
    # GSPMD all-gathers the FSDP-sharded weights every decode step —
    # measured 490 MB/layer collectives on qwen2-72b decode_32k
    # (EXPERIMENTS.md §Perf hillclimb A).
    decode_mode: bool = False

    @staticmethod
    def none() -> "Policy":
        return Policy(active=False)

    @staticmethod
    def for_mesh(mesh: jax.sharding.Mesh) -> "Policy":
        batch = (POD, DATA) if POD in mesh.axis_names else (DATA,)
        return Policy(active=True, batch_axes=batch, model_axis=MODEL)

    @property
    def b(self):
        """Batch-dim spec element (None when the batch can't be sharded)."""
        return self.batch_axes if self.batch_axes else None

    # -- activation constraints ------------------------------------------------
    def _constrain(self, x, spec):
        if not self.active:
            return x
        return jax.lax.with_sharding_constraint(x, spec)

    def act_btd(self, x):
        """(B, S, D) worked activations: batch sharded, d replicated on model
        (inputs/outputs of TP matmuls)."""
        if self.decode_mode:
            # batch replicated, d on data: transitions to/from the
            # batch-sharded attention path are activation-sized all-to-alls
            return self._constrain(x, P(None, None, DATA))
        return self._constrain(x, P(self.b, None, None))

    def act_btd_tp(self, x):
        """(B, S, D_shard) intermediate of a TP matmul: last dim on model."""
        return self._constrain(x, P(self.b, None, self.model_axis))

    def act_residual(self, x):
        """Residual stream between blocks: seq additionally on model (SP);
        decode (S=1): batch replicated, d on data (weight-stationary)."""
        if self.decode_mode:
            return self._constrain(x, P(None, None, DATA))
        if not self.seq_shard_residual:
            return self.act_btd(x)
        return self._constrain(x, P(self.b, self.model_axis, None))

    def act_heads(self, x):
        """(B, S, H, Dh): heads on model."""
        return self._constrain(x, P(self.b, None, self.model_axis, None))

    def kv_cache(self, x):
        """(B, S, H_kv, Dh) cache: batch on data, seq on model (flash-decode
        partial-softmax combines over model — DESIGN.md §4)."""
        return self._constrain(x, P(self.b, self.model_axis, None, None))

    def logits(self, x):
        """(B, S, V): vocab on model (pre-gather)."""
        return self._constrain(x, P(self.b, None, self.model_axis))


# ---------------------------------------------------------------------------
# Param partition rules — by leaf path regex, matching dims by name.
# Conventions: weights stored (in_dim, out_dim); stacked layer dim first.
# ---------------------------------------------------------------------------

# (regex over "/"-joined path, spec WITHOUT the stacked-layer dim)
_RULES: list[tuple[str, P]] = [
    # embeddings: (vocab, d) — vocab on model (TP), d on data (FSDP)
    (r"embed/tokens$", P(MODEL, DATA)),
    (r"lm_head$", P(DATA, MODEL)),       # (d, vocab)
    (r"pos_embed$", P(None, DATA)),
    # attention
    (r"attn/wq(/kernel)?$", P(DATA, MODEL)),
    (r"attn/wk(/kernel)?$", P(DATA, MODEL)),
    (r"attn/wv(/kernel)?$", P(DATA, MODEL)),
    (r"attn/wo(/kernel)?$", P(MODEL, DATA)),
    (r"attn/[bw][qkvo]_bias$", P(MODEL)),
    # dense mlp (swiglu/gelu)
    (r"mlp/w_(gate|up)(/kernel)?$", P(DATA, MODEL)),
    (r"mlp/w_down(/kernel)?$", P(MODEL, DATA)),
    # moe experts: (E, d, f) — f on model (TP inside expert), d on data
    (r"moe/shared/w_(gate|up)$", P(DATA, MODEL)),
    (r"moe/shared/w_down$", P(MODEL, DATA)),
    (r"moe/w_(gate|up)$", P(None, DATA, MODEL)),
    (r"moe/w_down$", P(None, MODEL, DATA)),
    (r"moe/router$", P(DATA, None)),
    (r"moe/shared_gate$", P(DATA)),
    # rwkv6 time/channel-mix projections: (d, d') → in on data, out on model
    (r"rwkv/cm/w_v$", P(MODEL, DATA)),    # (d_ff, d): f on model (TP out)
    (r"rwkv/.*w_(r|k|v|g)$", P(DATA, MODEL)),
    (r"rwkv/.*w_o$", P(MODEL, DATA)),
    # griffin recurrent block: branch projections + RG-LRU gates
    (r"rec/w_(y|x)$", P(DATA, MODEL)),
    (r"rec/w_o$", P(MODEL, DATA)),
    (r"rec/conv_w$", P(None, MODEL)),
    (r"rec/conv_b$", P(MODEL)),
    (r"rglru/w_[ai]$", P(DATA, MODEL)),
    (r"rglru/b_[ai]$", P(MODEL)),
    (r"rglru/lam$", P(MODEL)),
    # per-channel vectors (decays, mixes, norms over d_model): replicate
    (r".*(norm|scale|ln)[^/]*$", P()),
]


def _spec_for(path: str, ndim: int, stacked: bool) -> P:
    for pat, spec in _RULES:
        if re.search(pat, path):
            parts = tuple(spec)
            if stacked:
                parts = (None,) + parts
            # pad/truncate to ndim
            parts = parts[:ndim] + (None,) * max(0, ndim - len(parts))
            return P(*parts)
    return P()  # replicate by default (small vectors)


def param_specs(params: Any, stacked_prefixes: tuple[str, ...] = ("layers",)) -> Any:
    """Pytree of PartitionSpec mirroring ``params``.

    Leaves under a path starting with any of ``stacked_prefixes`` carry a
    leading stacked-layer dim that is never sharded.
    """
    flat = jax.tree_util.tree_flatten_with_path(params)
    specs = {}

    def keystr(kp):
        out = []
        for k in kp:
            if hasattr(k, "key"):
                out.append(str(k.key))
            elif hasattr(k, "idx"):
                out.append(str(k.idx))
            else:
                out.append(str(k))
        return "/".join(out)

    leaves, treedef = jax.tree_util.tree_flatten(params)
    paths = [keystr(kp) for kp, _ in flat[0]]
    out_leaves = []
    for path, leaf in zip(paths, [l for _, l in flat[0]]):
        stacked = any(path.startswith(p) for p in stacked_prefixes)
        out_leaves.append(_spec_for(path, leaf.ndim, stacked))
    return jax.tree_util.tree_unflatten(treedef, out_leaves)


def named_shardings(mesh: jax.sharding.Mesh, specs: Any) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda s: isinstance(s, P),
    )
