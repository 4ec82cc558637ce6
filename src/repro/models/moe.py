"""Mixture-of-Experts: grouped top-k routing, shared experts, two dispatch
engines.

Grouping (GShard/Switch semantics): tokens are reshaped (B, S, d) →
(G, T_g, d) with G = batch size, and ALL routing state (ranks, capacity,
dispatch tables) is per-group. The group dim carries the batch sharding, so
routing never synchronizes across devices — a global-cumsum dispatch was
measured at 80+ GiB/device on the 32k-prefill cells (EXPERIMENTS.md §Perf
iteration 0c); grouped dispatch is the fix and the industry default.

Expert sharding (DESIGN.md §4): expert weights (E, d, f) put ``f`` on the
``model`` axis (TP inside every expert — no expert-count divisibility
constraint; 8 or 60 experts both map onto 16-way TP) and ``d`` on ``data``
(FSDP). The collective profile equals the dense-MLP TP profile.

Dispatch engines (identical outputs incl. per-group drop behaviour):
  * ``einsum`` — GShard one-hot dispatch/combine einsums (baseline;
    O(T_g·E·C) extra work);
  * ``sort``   — capacity-slot scatter/gather (Megablocks-flavoured,
    O(T_g·k·d) data movement; the beyond-baseline engine).

Capacity: C = max(1, cf·T_g·k/E) per group. The capacity-slot algebra is
the same fixed-capacity scatter as the paper's inclusion lists
(core/indexing.py) — see DESIGN.md §5.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import activation, dense_init
from repro.sharding import DATA, Policy


def init_moe(rng, d_model, d_ff_expert, n_experts, *, n_shared=0,
             d_ff_shared=None, dtype=jnp.float32):
    ks = jax.random.split(rng, 8)
    p = {
        "router": dense_init(ks[0], d_model, n_experts, jnp.float32),
        "w_gate": dense_init(ks[1], n_experts * d_model, d_ff_expert,
                             dtype).reshape(n_experts, d_model, d_ff_expert),
        "w_up": dense_init(ks[2], n_experts * d_model, d_ff_expert,
                           dtype).reshape(n_experts, d_model, d_ff_expert),
        "w_down": dense_init(ks[3], n_experts * d_ff_expert, d_model,
                             dtype).reshape(n_experts, d_ff_expert, d_model),
    }
    if n_shared:
        d_sh = d_ff_shared or n_shared * d_ff_expert
        p["shared"] = {
            "w_gate": dense_init(ks[4], d_model, d_sh, dtype),
            "w_up": dense_init(ks[5], d_model, d_sh, dtype),
            "w_down": dense_init(ks[6], d_sh, d_model, dtype),
        }
        p["shared_gate"] = dense_init(ks[7], d_model, 1, dtype)
    return p


def _route(p, x, top_k, *, normalize=True):
    """x: (G, T, d) → (gates (G,T,k), experts (G,T,k), aux)."""
    logits = x.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)               # (G, T, E)
    gates, experts = jax.lax.top_k(probs, top_k)
    if normalize:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    e = probs.shape[-1]
    me = probs.mean((0, 1))
    ce = jax.nn.one_hot(experts[..., 0], e).mean((0, 1))
    # Switch aux loss factors; reduced to a scalar by the caller so the
    # sharded path can average me/ce across shards BEFORE the product
    return gates, experts, (me, ce)


def _slots(experts, top_k, e, capacity):
    """Per-group rank of each (token, k) within its expert.

    experts: (G, T, k) → (slot (G,T,k), keep (G,T,k)).

    Memory-light ranking: a (G,T·k,E) one-hot cumsum costs 7.8 GiB/device
    at qwen2-moe scale (E=60) — EXPERIMENTS.md §Perf iteration 0d. Instead:
    stable argsort by expert id, rank = position − start-of-expert-run,
    O(G·T·k) memory. Stable sort ⇒ identical token-order ranks (and drops)
    as the cumsum formulation.
    """
    g, t, k = experts.shape
    tk = t * k
    exp_f = experts.reshape(g, tk)
    gi = jnp.arange(g)[:, None]
    order = jnp.argsort(exp_f, axis=1, stable=True)        # (G, TK)
    sorted_exp = jnp.take_along_axis(exp_f, order, axis=1)
    counts = jnp.zeros((g, e), jnp.int32).at[gi, exp_f].add(1)
    starts = jnp.cumsum(counts, axis=1) - counts           # exclusive
    rank_sorted = (jnp.arange(tk, dtype=jnp.int32)[None]
                   - jnp.take_along_axis(starts, sorted_exp, axis=1))
    slot = jnp.zeros((g, tk), jnp.int32).at[gi, order].set(rank_sorted)
    slot = slot.reshape(g, t, k)
    return slot, slot < capacity


def _expert_ffn(p, h, act_fn, policy: Policy):
    """h: (G, E, C, d) → (G, E, C, d) through per-expert SwiGLU (TP on f).

    The output is constrained to d@model: the w_down contraction over
    f@model then resolves as reduce-scatter-sized traffic instead of a
    full (G,E,C,d) all-reduce — and, crucially, the combine-gather's
    BACKWARD scatter-add stays model-local (was a 640 MB fp32 all-reduce
    per layer per microbatch on mixtral train_4k — §Perf hillclimb B).
    """
    gate = jnp.einsum("gecd,edf->gecf", h, p["w_gate"].astype(h.dtype))
    up = jnp.einsum("gecd,edf->gecf", h, p["w_up"].astype(h.dtype))
    mid = act_fn(gate) * up
    if policy.active:
        mid = jax.lax.with_sharding_constraint(
            mid, jax.sharding.PartitionSpec(policy.b, None, None,
                                            policy.model_axis))
    out = jnp.einsum("gecf,efd->gecd", mid, p["w_down"].astype(h.dtype))
    if policy.active:
        out = jax.lax.with_sharding_constraint(
            out, jax.sharding.PartitionSpec(policy.b, None, None,
                                            policy.model_axis))
    return out


def moe_einsum(p, x, *, top_k, capacity, act="silu", policy: Policy,
               normalize=True):
    """GShard one-hot dispatch. x: (G, T, d) → (out (G,T,d), aux)."""
    g, t, d = x.shape
    e = p["router"].shape[-1]
    gates, experts, aux = _route(p, x, top_k, normalize=normalize)
    slot, keep = _slots(experts, top_k, e, capacity)
    oh_e = jax.nn.one_hot(experts, e, dtype=x.dtype)      # (G,T,k,E)
    oh_c = jax.nn.one_hot(jnp.where(keep, slot, capacity), capacity,
                          dtype=x.dtype)                  # (G,T,k,C)
    disp = jnp.einsum("gtke,gtkc->gtec", oh_e, oh_c)      # (G,T,E,C)
    h = jnp.einsum("gtec,gtd->gecd", disp, x)
    out_e = _expert_ffn(p, h, activation(act), policy)
    comb = jnp.einsum("gtke,gtkc,gtk->gtec", oh_e, oh_c,
                      gates.astype(x.dtype))
    out = jnp.einsum("gtec,gecd->gtd", comb, out_e)
    return out, aux


def moe_sort(p, x, *, top_k, capacity, act="silu", policy: Policy,
             normalize=True):
    """Capacity-slot scatter dispatch (no O(T·E·C) einsum). x: (G, T, d)."""
    g, t, d = x.shape
    e = p["router"].shape[-1]
    gates, experts, aux = _route(p, x, top_k, normalize=normalize)
    slot, keep = _slots(experts, top_k, e, capacity)

    exp_f = experts.reshape(g, t * top_k)
    slot_f = jnp.where(keep, slot, capacity).reshape(g, t * top_k)
    tok_f = jnp.broadcast_to(
        jnp.repeat(jnp.arange(t), top_k)[None], (g, t * top_k))
    # scatter token ids into per-group (E, C+1) slot tables
    table = jnp.full((g, e, capacity + 1), t, jnp.int32)
    gi = jnp.arange(g)[:, None]
    table = table.at[gi, exp_f, slot_f].set(tok_f.astype(jnp.int32),
                                            mode="drop")
    table = table[..., :capacity]                         # (G, E, C)
    x_pad = jnp.concatenate([x, jnp.zeros((g, 1, d), x.dtype)], axis=1)
    h = jnp.take_along_axis(
        x_pad[:, :, None, :],                             # (G, T+1, 1, d)
        table.reshape(g, e * capacity, 1, 1).clip(0, t),  # indices
        axis=1).reshape(g, e, capacity, d)
    out_e = _expert_ffn(p, h, activation(act), policy)
    out_flat = out_e.reshape(g, e * capacity, d)
    lin = jnp.where(keep, experts * capacity + slot,
                    e * capacity).reshape(g, t * top_k)
    out_pad = jnp.concatenate(
        [out_flat, jnp.zeros((g, 1, d), x.dtype)], axis=1)
    per_k = jnp.take_along_axis(
        out_pad[:, :, None, :], lin.reshape(g, t * top_k, 1, 1), axis=1)
    per_k = per_k.reshape(g, t, top_k, d)
    out = jnp.einsum("gtkd,gtk->gtd", per_k, gates.astype(x.dtype))
    return out, aux


def _moe_shard_map(p, xg, *, top_k, capacity, act, policy: Policy,
                   dispatch, normalize):
    """Explicit-collective MoE (hillclimb B, EXPERIMENTS.md §Perf).

    GSPMD placed the TP all-reduce at the capacity-inflated (G,E,C,d)
    expert output — and its BACKWARD emitted a fp32 all-reduce of the
    dispatch scatter-add (640 MB/layer/microbatch on mixtral train_4k).
    Here collectives are explicit and token-sized:

      * expert weights: one tiled all-gather over `data` (FSDP); its
        transpose is automatically a reduce-scatter of the weight grads;
      * routing/dispatch/ffn/combine: fully local (d is full, f is the
        local model shard — contraction over f-chunk makes the combined
        output a partial sum);
      * ONE psum over `model` of the (G_local, T, d) combined output.
    """
    mesh = jax.sharding.get_abstract_mesh()
    bb = policy.b
    m_axis = policy.model_axis
    engine = {"einsum": moe_einsum, "sort": moe_sort}[dispatch]
    local_policy = Policy.none()

    def body(xl, router, wg, wu, wd):
        # weights arrive with d sharded over `data` (FSDP): gather d —
        # w_gate/w_up (E, d/|data|, f_loc) axis=1; w_down (E, f_loc, d/…) axis=2
        p_local = {
            "router": jax.lax.all_gather(router, DATA, axis=0, tiled=True),
            "w_gate": jax.lax.all_gather(wg, DATA, axis=1, tiled=True),
            "w_up": jax.lax.all_gather(wu, DATA, axis=1, tiled=True),
            "w_down": jax.lax.all_gather(wd, DATA, axis=2, tiled=True),
        }
        out, (me, ce) = engine(p_local, xl, top_k=top_k, capacity=capacity,
                               act=act, policy=local_policy,
                               normalize=normalize)
        out = jax.lax.psum(out, m_axis)        # token-sized TP reduce
        if bb:                                  # exact global aux stats
            me = jax.lax.pmean(me, bb)
            ce = jax.lax.pmean(ce, bb)
        return out, me, ce

    out, me, ce = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bb, None, None),                 # xg: groups on batch axes
                  P(DATA, None),                     # router (d, E)
                  P(None, DATA, m_axis),             # w_gate (E, d, f)
                  P(None, DATA, m_axis),             # w_up
                  P(None, m_axis, DATA)),            # w_down (E, f, d)
        out_specs=(P(bb, None, None), P(), P()),
        check_vma=False,
    )(xg, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return out, (me, ce)


def moe_block(p, x, *, top_k, capacity_factor, act="silu", policy: Policy,
              dispatch="sort", normalize=True, num_groups=None,
              use_shard_map=True, dropless=False):
    """x: (B, S, d) → (out, aux). Groups = batch rows (GShard semantics);
    shared experts (if any) always active.

    ``dropless=True`` sizes capacity to the per-group token count — no
    token can ever overflow its expert (top-k experts are distinct, so an
    expert receives at most T_g tokens/group). Inference (prefill + decode)
    runs dropless: capacity dropping is a *training* regularizer, and a
    prefill that drops tokens can never agree with step-by-step decode,
    where each single-token group trivially fits (the mixtral
    prefill↔decode consistency bug). Costs up to E/(cf·k)× more expert-FFN
    buffer at prefill; decode (T_g = 1) is unchanged.
    """
    b, s, d = x.shape
    g = num_groups or b
    tg = (b * s) // g
    e = p["router"].shape[-1]
    capacity = tg if dropless else max(1, int(capacity_factor * tg * top_k / e))
    xg = x.reshape(g, tg, d)
    if policy.active:
        xg = jax.lax.with_sharding_constraint(
            xg, jax.sharding.PartitionSpec(policy.b, None, None))
    if policy.active and use_shard_map and policy.model_axis is not None:
        out, (me, ce) = _moe_shard_map(
            p, xg, top_k=top_k, capacity=capacity, act=act, policy=policy,
            dispatch=dispatch, normalize=normalize)
    else:
        fn = {"einsum": moe_einsum, "sort": moe_sort}[dispatch]
        out, (me, ce) = fn(p, xg, top_k=top_k, capacity=capacity, act=act,
                           policy=policy, normalize=normalize)
    aux = e * jnp.sum(me * ce)                   # Switch load-balance loss
    out = out.reshape(b, s, d)
    if "shared" in p:
        from repro.models.mlp import mlp
        sh = mlp(p["shared"], x, act=act, policy=policy)
        sg = jax.nn.sigmoid(x @ p["shared_gate"].astype(x.dtype))
        out = out + sg * sh
    return out, aux
