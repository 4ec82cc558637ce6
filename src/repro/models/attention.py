"""Attention: GQA with rope/qk-norm/bias, causal/sliding-window/full masks,
dense + blockwise(flash-scan) paths, KV caches, and distributed flash-decode
(partial-softmax combine over the seq-sharded ``model`` axis).

Cache layout (per layer): {"k": (B, S, Hkv, Dh), "v": same, "pos": (B, S)}
``pos`` is the absolute position stored in each slot (-1 = empty). Sliding
windows use rolling-buffer caches of size min(window, seq) (vLLM-style).
K is stored post-rope.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import apply_rope, dense_init, rmsnorm
from repro.sharding import Policy

NEG_INF = -2.0 ** 30  # large-but-finite: keeps masked softmax NaN-free


def init_attention(rng, d_model, n_heads, n_kv_heads, head_dim, *,
                   qkv_bias=False, qk_norm=False, dtype=jnp.float32):
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], d_model, n_heads * head_dim, dtype),
        "wk": dense_init(ks[1], d_model, n_kv_heads * head_dim, dtype),
        "wv": dense_init(ks[2], d_model, n_kv_heads * head_dim, dtype),
        "wo": dense_init(ks[3], n_heads * head_dim, d_model, dtype),
    }
    if qkv_bias:
        p["wq_bias"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["wk_bias"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
        p["wv_bias"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
    if qk_norm:
        p["q_norm"] = {"scale": jnp.ones((head_dim,), jnp.float32)}
        p["k_norm"] = {"scale": jnp.ones((head_dim,), jnp.float32)}
    return p


def _project_qkv(p, x, n_heads, n_kv_heads, head_dim, positions, theta,
                 use_rope=True):
    b, s, _ = x.shape
    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if "wq_bias" in p:
        q = q + p["wq_bias"].astype(x.dtype)
        k = k + p["wk_bias"].astype(x.dtype)
        v = v + p["wv_bias"].astype(x.dtype)
    q = q.reshape(b, s, n_heads, head_dim)
    k = k.reshape(b, s, n_kv_heads, head_dim)
    v = v.reshape(b, s, n_kv_heads, head_dim)
    if "q_norm" in p:  # qwen3-style per-head rms norm
        q = rmsnorm(p["q_norm"], q)
        k = rmsnorm(p["k_norm"], k)
    if use_rope:
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
    return q, k, v


def _mask(q_pos, k_pos, kind, window):
    """q_pos: (…, Sq), k_pos: (…, Sk) → bool (…, Sq, Sk) allowed."""
    dq = q_pos[..., :, None]
    dk = k_pos[..., None, :]
    ok = dk >= 0
    if kind == "causal":
        ok &= dk <= dq
        if window is not None:
            ok &= dk > dq - window
    elif kind == "full":
        pass
    else:
        raise ValueError(kind)
    return ok


def _repeat_kv(k, g):
    """(B,S,Hkv,Dh) → (B,S,H,Dh). Materialising the GQA repeat keeps every
    attention operand sharded H-ways on ``model`` — without it GSPMD mixes
    (Hkv, G) factorizations and falls back to full rematerialization
    (observed in dry-run iteration 0; see EXPERIMENTS.md §Perf)."""
    if g == 1:
        return k
    b, s, hkv, dh = k.shape
    return jnp.repeat(k, g, axis=2)


def _sdpa(q, k, v, mask, scale):
    """Dense attention. q: (B,Sq,H,Dh), k/v: (B,Sk,Hkv,Dh), mask (B,Sq,Sk)."""
    b, sq, h, dh = q.shape
    g = h // k.shape[2]
    k = _repeat_kv(k, g)
    v = _repeat_kv(v, g)
    scores = jnp.einsum("bqhd,bshd->bhqs", q, k) * scale   # (B,H,Sq,Sk)
    scores = jnp.where(mask[:, None], scores.astype(jnp.float32), NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqs,bshd->bqhd", w, v)
    return out


def _blockwise_sdpa(q, k, v, q_pos, k_pos, kind, window, scale, kv_block=512):
    """Flash-style attention: lax.scan over KV blocks with running
    (max, denom, acc) — O(S·kv_block) live memory instead of O(S²).

    Per-iteration cost is constant (full Q vs one KV block, masked), so the
    roofline harness treats the KV loop as a 'chunks' scale dim.
    """
    b, sq, h, dh = q.shape
    g = h // k.shape[2]
    k = _repeat_kv(k, g)
    v = _repeat_kv(v, g)
    sk = k.shape[1]
    pad = (-sk) % kv_block
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
    nb = k.shape[1] // kv_block
    kb = k.reshape(b, nb, kv_block, h, dh).swapaxes(0, 1)
    vb = v.reshape(b, nb, kv_block, h, dh).swapaxes(0, 1)
    pb = k_pos.reshape(b, nb, kv_block).swapaxes(0, 1)

    acc0 = jnp.zeros((b, sq, h, dh), jnp.float32)
    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)

    def body(carry, blk):
        acc, m, l = carry
        kc, vc, pc = blk
        s = jnp.einsum("bqhd,bshd->bhqs", q, kc).astype(jnp.float32) * scale
        ok = _mask(q_pos, pc, kind, window)               # (B, Sq, blk)
        s = jnp.where(ok[:, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(-1)
        acc_new = acc * alpha.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqs,bshd->bqhd", p.astype(q.dtype), vc
        ).astype(jnp.float32)
        return (acc_new, m_new, l_new), None

    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), (kb, vb, pb))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def attend(p, x, positions, *, n_heads, n_kv_heads, head_dim, rope_theta,
           kind="causal", window=None, use_rope=True, policy: Policy,
           dense_max_seq=8192, kv_block=512):
    """Full-sequence attention (training / prefill compute). x: (B,S,D)."""
    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, head_dim, positions,
                           rope_theta, use_rope)
    q = policy.act_heads(q)
    k = policy.act_heads(k)
    v = policy.act_heads(v)
    scale = head_dim ** -0.5
    pos2 = jnp.broadcast_to(positions if positions.ndim == 2
                            else positions[None], x.shape[:2])
    if x.shape[1] <= dense_max_seq:
        mask = _mask(pos2, pos2, kind, window)
        out = _sdpa(q, k, v, mask, scale)
    else:
        out = _blockwise_sdpa(q, k, v, pos2, pos2, kind, window, scale,
                              kv_block)
    out = out.reshape(*x.shape[:2], n_heads * head_dim)
    y = out @ p["wo"].astype(x.dtype)
    return y, (k, v)


def cross_attend(p, x, enc_kv, *, n_heads, n_kv_heads, head_dim,
                 policy: Policy):
    """Cross-attention to precomputed encoder K/V (whisper decoder)."""
    b, s, _ = x.shape
    q = (x @ p["wq"].astype(x.dtype)).reshape(b, s, n_heads, head_dim)
    k, v = enc_kv
    scale = head_dim ** -0.5
    mask = jnp.ones((b, s, k.shape[1]), bool)
    out = _sdpa(q, k, v, mask, scale).reshape(b, s, n_heads * head_dim)
    return out @ p["wo"].astype(x.dtype)


def encoder_kv(p, enc_out, *, n_kv_heads, head_dim):
    b, s, _ = enc_out.shape
    k = (enc_out @ p["wk"].astype(enc_out.dtype)).reshape(b, s, n_kv_heads, head_dim)
    v = (enc_out @ p["wv"].astype(enc_out.dtype)).reshape(b, s, n_kv_heads, head_dim)
    return k, v


# ---------------------------------------------------------------------------
# KV caches
# ---------------------------------------------------------------------------


def init_cache(batch, cache_len, n_kv_heads, head_dim, dtype=jnp.bfloat16):
    """Decode cache layout (B, Hkv, S, Dh): S-major-last-two matches the
    flash-decode dot layout, so no per-step cache transpose (a full cache
    copy per layer otherwise — §Perf hillclimb A)."""
    return {
        "k": jnp.zeros((batch, n_kv_heads, cache_len, head_dim), dtype),
        "v": jnp.zeros((batch, n_kv_heads, cache_len, head_dim), dtype),
        "pos": jnp.full((batch, cache_len), -1, jnp.int32),
    }


def cache_from_prefill(k, v, positions, cache_len):
    """Keep the trailing ``cache_len`` positions (rolling buffer for SWA).
    k, v: (B, S, Hkv, Dh) from the prefill pass → (B, Hkv, S', Dh) cache."""
    s = k.shape[1]
    kt = k.swapaxes(1, 2)                                 # (B, Hkv, S, Dh)
    vt = v.swapaxes(1, 2)
    pos2 = jnp.broadcast_to(positions if positions.ndim == 2
                            else positions[None], k.shape[:2])
    if s <= cache_len:
        pad = cache_len - s
        return {
            "k": jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0))),
            "v": jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0))),
            "pos": jnp.pad(pos2.astype(jnp.int32), ((0, 0), (0, pad)),
                           constant_values=-1),
        }
    # rolling placement: absolute position t lives in slot t % cache_len
    keep = jnp.arange(s - cache_len, s)
    slots = keep % cache_len
    b = k.shape[0]
    out = init_cache(b, cache_len, k.shape[2], k.shape[3], k.dtype)
    out["k"] = out["k"].at[:, :, slots].set(kt[:, :, keep])
    out["v"] = out["v"].at[:, :, slots].set(vt[:, :, keep])
    out["pos"] = out["pos"].at[:, slots].set(pos2[:, keep].astype(jnp.int32))
    return out


def _decode_attend_local(q, cache_k, cache_v, cache_pos, pos, scale):
    """Single-token attention vs a (local shard of a) cache.

    Returns un-normalised (acc, m, l) so shards can be combined
    (flash-decode partial-softmax algebra).
    q: (B, H, Dh); cache: (B, Hkv, S, Dh); pos: (B,) current position.
    fp32 accumulation via preferred_element_type — upcasting operands
    would materialise an f32 copy of the cache (§Perf hillclimb A).
    """
    b, h, dh = q.shape
    hkv = cache_k.shape[1]
    g = h // hkv
    qg = q.reshape(b, hkv, g, dh)
    s = jnp.einsum("bkgd,bksd->bkgs", qg, cache_k,
                   preferred_element_type=jnp.float32) * scale
    ok = (cache_pos >= 0) & (cache_pos <= pos[:, None])   # (B, S)
    s = jnp.where(ok[:, None, None], s, NEG_INF)
    m = s.max(-1)                                         # (B,Hkv,G)
    p = jnp.exp(s - m[..., None])
    l = p.sum(-1)
    acc = jnp.einsum("bkgs,bksd->bkgd", p.astype(cache_v.dtype), cache_v,
                     preferred_element_type=jnp.float32)
    return acc, m, l


def decode_attend(p, x, cache, pos, *, n_heads, n_kv_heads, head_dim,
                  rope_theta, window, use_rope=True, policy: Policy):
    """One-token decode: x (B, 1, D), cache seq-sharded over ``model``.

    With an active mesh, runs the partial-softmax combine as a shard_map
    over the model axis (each shard scores its cache slice; softmax stats
    are merged with the flash-decode rescaling identity). Mathematically
    exact — tests pin it against the dense path.
    """
    b = x.shape[0]
    positions = pos[:, None] if pos.ndim == 1 else pos
    q, k_new, v_new = _project_qkv(p, x, n_heads, n_kv_heads, head_dim,
                                   positions, rope_theta, use_rope)
    q = q[:, 0]                                           # (B, H, Dh)
    cache_len = cache["k"].shape[2]                       # (B, Hkv, S, Dh)
    scale = head_dim ** -0.5
    pos_b = positions[:, 0]

    if policy.active and policy.model_axis is not None:
        # Fused update+attend shard_map over the seq-sharded cache. The
        # scatter is SHARD-LOCAL (each seq shard masks whether the slot
        # lands in its slice): a global `.at[b, slot].set` on a sharded
        # dim made GSPMD reshard the whole cache every layer — measured
        # 3.97 GB bytes + 490 MB collectives per layer on qwen2-72b
        # decode_32k vs ~75 MB of cache physics (§Perf hillclimb A).
        mesh = jax.sharding.get_abstract_mesh()
        axis = policy.model_axis
        bb = policy.b

        def shard_fn(q_, kn, vn, ck, cv, cp, pb):
            s_local = ck.shape[2]
            start = jax.lax.axis_index(axis) * s_local
            slot = (pb % cache_len).astype(jnp.int32) - start  # (B,)
            mine = (slot >= 0) & (slot < s_local)
            slot_safe = jnp.clip(slot, 0, s_local - 1)
            nb, nh = ck.shape[0], ck.shape[1]
            bidx = jnp.arange(nb)[:, None]
            hidx = jnp.arange(nh)[None, :]
            sidx = slot_safe[:, None]
            ck = ck.at[bidx, hidx, sidx].set(
                jnp.where(mine[:, None, None], kn.astype(ck.dtype),
                          ck[bidx, hidx, sidx]))
            cv = cv.at[bidx, hidx, sidx].set(
                jnp.where(mine[:, None, None], vn.astype(cv.dtype),
                          cv[bidx, hidx, sidx]))
            cp = cp.at[jnp.arange(nb), slot_safe].set(
                jnp.where(mine, pb.astype(jnp.int32),
                          cp[jnp.arange(nb), slot_safe]))
            cpos = cp
            if window is not None:
                cpos = jnp.where(cp > (pb[:, None] - window), cp, -1)
            acc, m, l = _decode_attend_local(q_, ck, cv, cpos, pb, scale)
            m_g = jax.lax.pmax(m, axis)
            corr = jnp.exp(m - m_g)
            l_g = jax.lax.psum(l * corr, axis)
            acc_g = jax.lax.psum(acc * corr[..., None], axis)
            out = acc_g / jnp.maximum(l_g, 1e-30)[..., None]
            return out, ck, cv, cp

        out, new_k, new_v, new_p = jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(P(bb, None, None),
                      P(bb, None, None), P(bb, None, None),
                      P(bb, None, axis, None),
                      P(bb, None, axis, None),
                      P(bb, axis),
                      P(bb)),
            out_specs=(P(bb, None, None, None),
                       P(bb, None, axis, None),
                       P(bb, None, axis, None),
                       P(bb, axis)),
            check_vma=False,
        )(q, k_new[:, 0], v_new[:, 0], cache["k"], cache["v"],
          cache["pos"], pos_b)
        cache = {"k": new_k, "v": new_v, "pos": new_p}
    else:
        slot = (pos_b % cache_len).astype(jnp.int32)
        nb, nh = cache["k"].shape[0], cache["k"].shape[1]
        bidx = jnp.arange(nb)[:, None]
        hidx = jnp.arange(nh)[None, :]
        sidx = slot[:, None]
        cache = {
            "k": cache["k"].at[bidx, hidx, sidx].set(
                k_new[:, 0].astype(cache["k"].dtype)),
            "v": cache["v"].at[bidx, hidx, sidx].set(
                v_new[:, 0].astype(cache["v"].dtype)),
            "pos": cache["pos"].at[jnp.arange(nb), slot].set(
                pos_b.astype(jnp.int32)),
        }
        if window is not None:
            cpos = jnp.where(cache["pos"] > (pos_b[:, None] - window),
                             cache["pos"], -1)
        else:
            cpos = cache["pos"]
        acc, m, l = _decode_attend_local(q, cache["k"], cache["v"], cpos,
                                         pos_b, scale)
        out = acc / jnp.maximum(l, 1e-30)[..., None]

    out = out.reshape(b, 1, n_heads * head_dim).astype(x.dtype)
    y = out @ p["wo"].astype(x.dtype)
    return y, cache
