import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import (jax locks the device
# count at first init). Everything below may import jax.

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from pathlib import Path # noqa: E402

import jax               # noqa: E402

from repro.configs import (  # noqa: E402
    ARCHS, SKIPPED_CELLS, get_config, get_shape, shapes_for)
from repro.launch import hlo as hlo_mod  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.sharding import named_shardings  # noqa: E402
from repro.steps import make_step  # noqa: E402

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               step_kwargs=None, cfg_override=None, save=True,
               tag="baseline"):
    """Lower + compile one (arch × shape × mesh) cell; returns the record.

    This is deliverable (e): ``.lower().compile()`` must succeed for every
    cell; memory_analysis proves fit, cost_analysis feeds §Roofline.
    """
    cfg = get_config(arch)
    if cfg_override:
        cfg = dataclasses.replace(cfg, **cfg_override)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    step = make_step(cfg, shape, mesh, **(step_kwargs or {}))
    in_sh = named_shardings(mesh, step.in_specs)
    out_sh = named_shardings(mesh, step.out_specs)
    # donate what the next step overwrites: train → state, decode → caches;
    # serving params are shared across steps and must never be donated.
    donate = {"train": (0,), "decode": (1,), "prefill": ()}[step.meta["kind"]]
    with jax.set_mesh(mesh):
        jitted = jax.jit(step.fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*step.arg_structs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    text = compiled.as_text()
    coll = hlo_mod.collective_stats(text)
    n_dev = mesh.devices.size
    record = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "devices": n_dev,
        "tag": tag,
        "memory": {
            "argument_bytes_per_device": ma.argument_size_in_bytes,
            "output_bytes_per_device": ma.output_size_in_bytes,
            "temp_bytes_per_device": ma.temp_size_in_bytes,
            "alias_bytes_per_device": ma.alias_size_in_bytes,
            "peak_estimate_per_device": (
                ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes),
        },
        "cost": {
            "flops_per_device_hlo": ca.get("flops", 0.0),
            "bytes_accessed_per_device_hlo": ca.get("bytes accessed", 0.0),
        },
        "collectives": {
            "total_bytes": coll.total_bytes,
            "by_kind": coll.by_kind,
            "count": coll.count,
        },
        "loop_dims": step.loop_dims,
        "meta": step.meta,
        "times": {"lower_s": round(t_lower, 2),
                  "compile_s": round(t_compile, 2)},
    }
    if save:
        out = RESULTS / arch / shape_name
        out.mkdir(parents=True, exist_ok=True)
        fn = out / f"{record['mesh']}.{tag}.json"
        fn.write_text(json.dumps(record, indent=2))
        # keep a trimmed collective schedule for §Dry-run
        (out / f"{record['mesh']}.{tag}.schedule.txt").write_text(
            "\n".join(f"{k} {b} {rg}" for k, b, rg in coll.schedule[:400]))
    return record


def run_tm_checks(*, data: int = 2, model: int = 4, n_clauses: int = 256,
                  batch: int = 16, train_batch: int = 8, save: bool = True,
                  expect_composition: str | None = None) -> dict:
    """Lower + compile the clause-sharded TM path; assert the vote HLO.

    For every registered engine: the sharded ``scores`` program must contain
    **exactly one** collective, and it must be the (B, m) vote all-reduce —
    the Massively Parallel TM contract (DESIGN.md §6). The sharded
    ``train_step`` may psum a vote per class round (+ delta reductions in
    parallel mode) but must never gather state or caches: every collective
    has to be an all-reduce.

    Backend routes (DESIGN.md §8): the packed engine is additionally lowered
    per kernel backend — under ``pallas_interpret`` the shard-local
    evaluator must *be* the Pallas kernel (``pallas_call`` in the jaxpr)
    while the program still contains only the single vote all-reduce; under
    ``xla`` no kernel call may appear.

    Ragged routes (DESIGN.md §9): ``n_clauses`` need not divide by either
    mesh axis. The sequential train record names which composition rule
    fired (``composed_even`` / ``composed_ragged`` / ``replicated``);
    ``expect_composition`` records a failure when a different rule fires —
    the CI cell pins a previously-indivisible shape onto
    ``composed_ragged`` with the collective profile unchanged
    (all-reduce-only, one vote all-reduce for scores).
    """
    import jax.numpy as jnp

    from repro.core import TMConfig, registered_engines
    from repro.core.distributed import (
        geometry, make_sharded_prepare, make_sharded_scores,
        make_sharded_train_step)
    from repro.core.engines import get_engine
    from repro.core.types import init_tm
    from repro.launch.mesh import make_host_mesh

    cfg = TMConfig(n_classes=10, n_clauses=n_clauses, n_features=196)
    mesh = make_host_mesh(data=data, model=model)
    geom = geometry(cfg, mesh)
    bundle = make_sharded_prepare(cfg, mesh)(init_tm(cfg))
    xs = jnp.zeros((batch, cfg.n_features), jnp.uint8)
    record: dict = {"mesh": f"{data}x{model}", "n_clauses": n_clauses,
                    "geometry": {"n_local": geom.n_local,
                                 "n_padded": geom.n_padded,
                                 "n_sub": geom.n_sub,
                                 "ragged_clauses": geom.ragged_clauses},
                    "engines": {}, "backend_routes": {}, "failures": []}

    for name in registered_engines():
        eng = get_engine(name)
        s = make_sharded_scores(cfg, mesh, engine=name)
        cache = (bundle.state if not eng.needs_cache
                 else bundle.caches[eng.cache_key])
        compiled = s.jitted.lower(cache, s.pol, xs).compile()
        coll = hlo_mod.collective_stats(compiled.as_text())
        ok = coll.count == 1 and set(coll.by_kind) == {"all-reduce"}
        record["engines"][name] = {
            "collective_count": coll.count, "by_kind": coll.by_kind,
            "one_vote_all_reduce": ok}
        print(f"[tm] scores/{name}: collectives={coll.by_kind} "
              f"count={coll.count} {'OK' if ok else 'FAIL'}", flush=True)
        if not ok:
            record["failures"].append(
                f"scores/{name}: expected exactly one vote all-reduce, got "
                f"{coll.by_kind} (count={coll.count})")

    # -- kernel backend routes for the packed engine ------------------------
    pcache = bundle.caches[get_engine("bitpack").cache_key]
    for backend in ("xla", "pallas_interpret"):
        cfg_b = dataclasses.replace(cfg, backend=backend)
        s = make_sharded_scores(cfg_b, mesh, engine="bitpack")
        jaxpr = str(jax.make_jaxpr(s.jitted)(pcache, s.pol, xs))
        kernel_routed = "pallas_call" in jaxpr
        coll = hlo_mod.collective_stats(
            s.jitted.lower(pcache, s.pol, xs).compile().as_text())
        one_ar = coll.count == 1 and set(coll.by_kind) == {"all-reduce"}
        want_kernel = backend != "xla"
        ok = one_ar and kernel_routed == want_kernel
        record["backend_routes"][backend] = {
            "pallas_call_in_jaxpr": kernel_routed,
            "collective_count": coll.count, "by_kind": coll.by_kind,
            "one_vote_all_reduce": one_ar}
        print(f"[tm] scores/bitpack[{backend}]: pallas_call={kernel_routed} "
              f"collectives={coll.by_kind} count={coll.count} "
              f"{'OK' if ok else 'FAIL'}", flush=True)
        if not ok:
            record["failures"].append(
                f"scores/bitpack[{backend}]: expected "
                f"{'the Pallas kernel' if want_kernel else 'the XLA body'} "
                f"with one vote all-reduce, got pallas_call={kernel_routed}, "
                f"{coll.by_kind} (count={coll.count})")

    # -- kernel backend routes for the indexed engine -----------------------
    # Matmul-form Eq. 4 (indexed_votes) must route exactly like clause_votes:
    # pallas_call in the jaxpr ⇔ a pallas backend, one vote all-reduce either
    # way. The train leg covers the second new primitive: index maintenance
    # (index_update) is the same scatter-bound batched-replay body on both
    # routes, and the step's collective profile must stay all-reduce-only
    # regardless of backend.
    icache = bundle.caches[get_engine("indexed").cache_key]
    btxs = jnp.zeros((train_batch, cfg.n_features), jnp.uint8)
    btys = jnp.zeros((train_batch,), jnp.int32)
    btmask = jnp.ones((train_batch,), bool)
    bkd = jax.random.key_data(jax.random.key(0))
    for backend in ("xla", "pallas_interpret"):
        cfg_b = dataclasses.replace(cfg, backend=backend)
        s = make_sharded_scores(cfg_b, mesh, engine="indexed")
        jaxpr = str(jax.make_jaxpr(s.jitted)(icache, s.pol, xs))
        kernel_routed = "pallas_call" in jaxpr
        coll = hlo_mod.collective_stats(
            s.jitted.lower(icache, s.pol, xs).compile().as_text())
        one_ar = coll.count == 1 and set(coll.by_kind) == {"all-reduce"}
        want_kernel = backend != "xla"
        tstep = make_sharded_train_step(cfg_b, mesh, parallel=False,
                                        max_events=1024)
        tcoll = hlo_mod.collective_stats(
            tstep.jitted.lower(bundle.state, bundle.caches, tstep.pol, btxs,
                               btys, bkd, btmask,
                               jnp.zeros((), jnp.int32)).compile().as_text())
        update_ok = set(tcoll.by_kind) <= {"all-reduce"}
        ok = one_ar and kernel_routed == want_kernel and update_ok
        record["backend_routes"][f"indexed_{backend}"] = {
            "pallas_call_in_jaxpr": kernel_routed,
            "collective_count": coll.count, "by_kind": coll.by_kind,
            "one_vote_all_reduce": one_ar,
            "train_step_all_reduce_only": update_ok,
            "train_step_by_kind": tcoll.by_kind}
        print(f"[tm] scores/indexed[{backend}]: pallas_call={kernel_routed} "
              f"collectives={coll.by_kind} count={coll.count} "
              f"train={tcoll.by_kind} {'OK' if ok else 'FAIL'}", flush=True)
        if not ok:
            record["failures"].append(
                f"scores/indexed[{backend}]: expected "
                f"{'the Pallas kernel' if want_kernel else 'the XLA body'} "
                f"with one vote all-reduce and an all-reduce-only train "
                f"step, got pallas_call={kernel_routed}, {coll.by_kind} "
                f"(count={coll.count}), train={tcoll.by_kind}")

    for parallel in (False, True):
        step = make_sharded_train_step(cfg, mesh, parallel=parallel,
                                       max_events=1024)
        txs = jnp.zeros((train_batch, cfg.n_features), jnp.uint8)
        tys = jnp.zeros((train_batch,), jnp.int32)
        tmask = jnp.ones((train_batch,), bool)
        kd = jax.random.key_data(jax.random.key(0))
        overflow0 = jnp.zeros((), jnp.int32)
        compiled = step.jitted.lower(bundle.state, bundle.caches, step.pol,
                                     txs, tys, kd, tmask, overflow0).compile()
        coll = hlo_mod.collective_stats(compiled.as_text())
        # sequential composes data×clause here (even or ragged sub-slices):
        # its clause-slice reassembly psum is an all-reduce too — the
        # contract stays "all-reduce only", never a gather of state/caches
        ok = set(coll.by_kind) <= {"all-reduce"}
        key = f"train_step_{'parallel' if parallel else 'sequential'}"
        record[key] = {"collective_count": coll.count,
                       "by_kind": coll.by_kind, "all_reduce_only": ok,
                       "composition": step.composition}
        print(f"[tm] {key}: collectives={coll.by_kind} count={coll.count} "
              f"composition={step.composition} {'OK' if ok else 'FAIL'}",
              flush=True)
        if not ok:
            record["failures"].append(
                f"{key}: feedback must stay shard-local — found "
                f"{coll.by_kind}")
        if (not parallel and expect_composition is not None
                and step.composition != expect_composition):
            record["failures"].append(
                f"{key}: expected composition rule {expect_composition!r}, "
                f"fired {step.composition!r}")

    if save:
        out = RESULTS / "tm"
        out.mkdir(parents=True, exist_ok=True)
        (out / f"{record['mesh']}.json").write_text(
            json.dumps(record, indent=2))
    return record


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--tm", action="store_true",
                    help="clause-sharded TM lowering checks (every engine; "
                         "asserts the single vote all-reduce)")
    args = ap.parse_args()

    if args.tm:
        # the PR-3 even cell + a previously-indivisible ragged cell
        # (n_clauses=128 over 3 clause shards × 2 data ranks — DESIGN.md §9):
        # both must lower to the same collective profile, and the ragged one
        # must fire the composed_ragged rule, not the replication fallback
        records = [
            run_tm_checks(expect_composition="composed_even"),
            run_tm_checks(data=2, model=3, n_clauses=128,
                          expect_composition="composed_ragged"),
        ]
        failures = [f for r in records for f in r["failures"]]
        if failures:
            print(f"\n{len(failures)} TM FAILURES:")
            for f in failures:
                print("  ", f)
            raise SystemExit(1)
        print("\nTM sharded lowering: all engines OK "
              "(one vote all-reduce; shard-local feedback; "
              "composition rules: "
              + ", ".join(f"{r['mesh']}→"
                          f"{r['train_step_sequential']['composition']}"
                          for r in records)
              + ")")
        return

    cells = []
    archs = ARCHS if (args.all or not args.arch) else (args.arch,)
    for arch in archs:
        cfg = get_config(arch)
        for shape in shapes_for(cfg):
            if args.shape and shape.name != args.shape:
                continue
            cells.append((arch, shape.name))
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for arch, shape_name in cells:
        for multi in meshes:
            mesh_name = "2x16x16" if multi else "16x16"
            out = RESULTS / arch / shape_name / f"{mesh_name}.baseline.json"
            if out.exists():
                print(f"[skip-cached] {arch} × {shape_name} × {mesh_name}")
                continue
            print(f"[dryrun] {arch} × {shape_name} × {mesh_name} ...",
                  flush=True)
            try:
                rec = lower_cell(arch, shape_name, multi_pod=multi)
                mem = rec["memory"]["peak_estimate_per_device"] / 2**30
                print(f"  ok: peak≈{mem:.2f} GiB/dev, "
                      f"flops={rec['cost']['flops_per_device_hlo']:.3g}, "
                      f"coll={rec['collectives']['total_bytes']:.3g}B, "
                      f"compile={rec['times']['compile_s']}s", flush=True)
            except Exception as e:  # noqa: BLE001 — report and continue
                failures.append((arch, shape_name, mesh_name, repr(e)))
                print(f"  FAIL: {e}\n{traceback.format_exc()}", flush=True)
    skipped = [f"{a} × {s}: {why}" for (a, s), why in SKIPPED_CELLS.items()]
    print("\nskipped cells (per DESIGN.md §5):")
    for s in skipped:
        print("  " + s)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nall requested cells lowered + compiled OK")


if __name__ == "__main__":
    main()
