"""Roofline analysis: three terms per (arch × shape × mesh) cell.

Hardware (TPU v5e, per brief): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.

    compute term    = FLOPs_per_device / 197e12
    memory term     = HBM_bytes_per_device / 819e9
    collective term = collective_bytes_per_device / 50e9

XLA's cost model counts while-loop bodies ONCE (verified; DESIGN.md §6), so
FLOPs/bytes/collectives are reconstructed from *probe* compiles that unroll
every loop:

  probes (train): (M=1, L=1), (M=2, L=1), (M=1, L=2)  [+ enc dim for encdec]
  model:  cost(M, L…) = c0 + M · (c1 + Σ_d L_d · c2_d)
  solve:  c2_d = f_d − f_base;  c0 = 2·f_base − f_M2;  c1 = f_base − c0 − Σ c2_d
  full:   c0 + M_full · (c1 + Σ_d L_d_full · c2_d)

Probe configs additionally run single-chunk (kv_block=seq, rwkv/rnn chunk =
seq, dense attention) so no inner scan hides cost. Chunk bookkeeping deltas
vs the production chunked program are O(chunks) adds — negligible.

MODEL_FLOPS (useful-work yardstick): 6·N·D (train) / 2·N·D (inference),
N = params (dense) or active params (MoE), D = tokens processed.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import jax

from repro.configs import get_config, get_shape
from repro.configs.base import ModelConfig, ShapeSpec
from repro.launch import hlo as hlo_mod
from repro.launch.mesh import make_production_mesh
from repro.sharding import named_shardings
from repro.steps import make_step

PEAK_FLOPS = 197e12      # bf16 / chip
HBM_BW = 819e9           # B/s / chip
ICI_BW = 50e9            # B/s / link

RESULTS = Path(__file__).resolve().parents[3] / "results" / "roofline"


# ---------------------------------------------------------------------------
# Probe machinery
# ---------------------------------------------------------------------------


def _probe_cfg(cfg: ModelConfig, shape: ShapeSpec, layer_overrides: dict):
    """Unrolled probe config with the given layer counts.

    Attention: kv_block=seq (single flash iteration ⇒ exact count; the
    score bytes touched are the same as production's blockwise form).
    Recurrences: production-style chunking with the chunk loop UNROLLED
    (use_scan=False threads through models/recurrence.py) — a full-seq
    single chunk would hand the associative scan T-length log-depth temps
    and inflate the memory term ~20× (observed on rwkv6 train probes).
    Chunk sizes are raised so ≤32 chunk bodies unroll per layer.
    """
    upd: dict = dict(
        use_scan=False,
        remat=False,                     # probes measure true per-layer cost;
        # remat recompute shows up in the full-compile cross-check instead.
        kv_block=shape.seq_len,
        dense_attn_max=max(cfg.dense_attn_max, shape.seq_len),
        rwkv_chunk=max(cfg.rwkv_chunk, -(-shape.seq_len // 32)),
        rnn_chunk=max(cfg.rnn_chunk, -(-shape.seq_len // 32)),
    )
    if cfg.family == "hybrid":
        pat = cfg.pattern or ("rec", "rec", "attn")
        g = layer_overrides.get("layers", 1)
        upd["n_layers"] = len(pat) * g + cfg.n_layers % len(pat)
    else:
        upd["n_layers"] = layer_overrides.get("layers", 1)
    if cfg.family == "encdec":
        upd["n_enc_layers"] = layer_overrides.get("enc_layers", 1)
    return dataclasses.replace(cfg, **upd)


def _measure(cfg, shape, mesh, *, microbatches, kind):
    """Lower+compile one probe; return dict of cost scalars (per device)."""
    kw = {}
    if kind == "train":
        kw["microbatches"] = microbatches
        kw["compress"] = "none"
    shape_p = shape
    if kind == "train":
        # probe batch = microbatch_size × M so per-microbatch work matches
        mb_size = shape.global_batch // 8  # production microbatch count = 8
        shape_p = dataclasses.replace(
            shape, global_batch=mb_size * microbatches)
    step = make_step(cfg, shape_p, mesh, **kw)
    in_sh = named_shardings(mesh, step.in_specs)
    out_sh = named_shardings(mesh, step.out_specs)
    with jax.set_mesh(mesh):
        compiled = (
            jax.jit(step.fn, in_shardings=in_sh, out_shardings=out_sh)
            .lower(*step.arg_structs).compile())
    ca = compiled.cost_analysis() or {}
    coll = hlo_mod.collective_stats(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll_bytes": float(coll.total_bytes),
    }


def probe_costs(arch: str, shape_name: str, *, multi_pod=False,
                cfg_override=None, microbatches_full=8, verbose=True):
    """Run the probe set and reconstruct full-program costs per device."""
    cfg = get_config(arch)
    if cfg_override:
        cfg = dataclasses.replace(cfg, **cfg_override)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    kind = shape.kind

    layer_dims = ["layers"] + (["enc_layers"] if cfg.family == "encdec"
                               else [])
    full_counts = {"layers": (cfg.n_layers // len(cfg.pattern or (1,))
                              if cfg.family == "hybrid" else cfg.n_layers)}
    if cfg.family == "hybrid":
        pat = cfg.pattern or ("rec", "rec", "attn")
        full_counts["layers"] = cfg.n_layers // len(pat)
    if cfg.family == "encdec":
        full_counts["enc_layers"] = cfg.n_enc_layers

    base_cfg = _probe_cfg(cfg, shape, {d: 1 for d in layer_dims})
    f_base = _measure(base_cfg, shape, mesh, microbatches=1, kind=kind)
    if verbose:
        print(f"  probe base: {f_base}", flush=True)

    c2 = {}
    for d in layer_dims:
        ov = {dd: (2 if dd == d else 1) for dd in layer_dims}
        f_d = _measure(_probe_cfg(cfg, shape, ov), shape, mesh,
                       microbatches=1, kind=kind)
        c2[d] = {k: f_d[k] - f_base[k] for k in f_base}
        if verbose:
            print(f"  probe {d}=2: {f_d}", flush=True)

    if kind == "train":
        f_m2 = _measure(base_cfg, shape, mesh, microbatches=2, kind=kind)
        if verbose:
            print(f"  probe M=2: {f_m2}", flush=True)
        c0 = {k: 2 * f_base[k] - f_m2[k] for k in f_base}
        c1 = {k: f_base[k] - c0[k] - sum(c2[d][k] for d in layer_dims)
              for k in f_base}
        m_full = microbatches_full
    else:
        c0 = {k: f_base[k] - sum(c2[d][k] for d in layer_dims)
              for k in f_base}
        c1 = {k: 0.0 for k in f_base}
        m_full = 1

    total = {
        k: c0[k] + m_full * (c1[k] + sum(
            full_counts[d] * c2[d][k] for d in layer_dims))
        for k in f_base
    }
    return {
        "per_device": total,
        "probe_coeffs": {"c0": c0, "c1": c1,
                         "c2": c2, "m_full": m_full,
                         "full_counts": full_counts},
    }


# ---------------------------------------------------------------------------
# Analytic MODEL_FLOPS
# ---------------------------------------------------------------------------


def model_flops(cfg: ModelConfig, shape: ShapeSpec) -> float:
    n = (cfg.active_param_count() if cfg.family == "moe"
         else cfg.param_count())
    if shape.kind == "train":
        return 6.0 * n * shape.tokens
    if shape.kind == "prefill":
        return 2.0 * n * shape.tokens
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


# ---------------------------------------------------------------------------
# Report assembly
# ---------------------------------------------------------------------------


def roofline_terms(per_device: dict) -> dict:
    comp = per_device["flops"] / PEAK_FLOPS
    mem = per_device["bytes"] / HBM_BW
    coll = per_device["coll_bytes"] / ICI_BW
    dom = max(("compute", comp), ("memory", mem), ("collective", coll),
              key=lambda kv: kv[1])[0]
    return {
        "compute_s": comp, "memory_s": mem, "collective_s": coll,
        "dominant": dom,
        "step_lower_bound_s": max(comp, mem, coll),
    }


def analyze_cell(arch: str, shape_name: str, *, multi_pod=False,
                 cfg_override=None, tag="baseline", save=True, verbose=True):
    cfg = get_config(arch)
    if cfg_override:
        cfg = dataclasses.replace(cfg, **cfg_override)
    shape = get_shape(shape_name)
    n_dev = 512 if multi_pod else 256
    costs = probe_costs(arch, shape_name, multi_pod=multi_pod,
                        cfg_override=cfg_override, verbose=verbose)
    terms = roofline_terms(costs["per_device"])
    mf = model_flops(cfg, shape)
    mf_dev = mf / n_dev
    useful = mf_dev / max(costs["per_device"]["flops"], 1e-9)
    record = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "tag": tag,
        "per_device": costs["per_device"],
        "terms": terms,
        "model_flops_total": mf,
        "model_flops_per_device": mf_dev,
        "useful_flops_ratio": useful,
        "roofline_fraction": min(1.0, useful) * (
            terms["compute_s"] / max(terms["step_lower_bound_s"], 1e-30)),
        "probe_coeffs": costs["probe_coeffs"],
    }
    if save:
        out = RESULTS / arch / shape_name
        out.mkdir(parents=True, exist_ok=True)
        (out / f"{record['mesh']}.{tag}.json").write_text(
            json.dumps(record, indent=2))
    return record


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--tag", default="baseline")
    args = ap.parse_args()
    rec = analyze_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                       tag=args.tag)
    print(json.dumps({k: v for k, v in rec.items()
                      if k != "probe_coeffs"}, indent=2))


if __name__ == "__main__":
    main()
