#!/usr/bin/env bash
# CI smoke: tier-1 tests + the quickstart example on the estimator API +
# the TM serving smoke + the sharded-lowering dry run. Run from anywhere:
#
#     bash scripts/ci.sh
#
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== hygiene: no compiled artifacts tracked =="
if git ls-files | grep -q '\.pyc$'; then
  echo "ERROR: *.pyc files are git-tracked:" >&2
  git ls-files | grep '\.pyc$' >&2
  exit 1
fi

echo "== docstring gate (public TM surface, README satellite) =="
python scripts/check_docstrings.py

echo "== tier-1 tests =="
# The seed's mixtral prefill/decode deselect is gone: inference MoE routing
# is dropless now (models/moe.py), so prefill and step-wise decode agree.
# The `slow` subprocess tests (sharding, TM sharded/session/backends/ragged
# parity) put this gate at ~40 min on the 1-core container; use
# `pytest -m "not slow"` for a fast local loop (pytest.ini).
python -m pytest -x -q

echo "== README quickstart (executed from the doc, never drifts) =="
python - <<'EOF'
import pathlib, re
text = pathlib.Path("README.md").read_text()
m = re.search(r"<!-- ci-quickstart -->\s*```python\n(.*?)```", text, re.S)
assert m, "no <!-- ci-quickstart --> python block in README.md"
exec(compile(m.group(1), "README.md#quickstart", "exec"),
     {"__name__": "__main__"})
EOF

echo "== quickstart (TsetlinMachine estimator API) =="
python examples/quickstart.py

echo "== tm_serve smoke (async serving runtime, sharded Pallas-interpret, 4-device mesh) =="
rm -f BENCH_tm_serve.json
XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  python -m repro.launch.tm_serve --smoke --backend pallas_interpret
python - <<'EOF'
import json
d = json.load(open("BENCH_tm_serve.json"))
assert d["engines"], "no engine records in BENCH_tm_serve.json"
assert d["schema"] == 2, f"expected schema 2, got {d.get('schema')}"
# the smoke must exercise the sharded scores path on the 4-device mesh and
# record the device count + per-device-count batch-axis scaling, serving the
# packed engine through the Pallas-interpret kernel route
assert d["devices"] == 4, f"device count not recorded: {d.get('devices')}"
assert d["topology"]["sharded"], d["topology"]
assert d["topology"]["backend"] == "pallas_interpret", d["topology"]
# §9: the fired composition rule is part of the topology metadata
assert d["topology"]["composition"] in (
    "composed_even", "composed_ragged", "clause_only"), d["topology"]
# §9: the per-shard row census (where ragged padding lands)
rows = d["topology"]["shard_rows"]
assert len(rows) == d["topology"]["clause_shards"], rows
assert all({"shard", "real_rows", "pad_rows"} <= set(r) for r in rows), rows
assert "bitpack" in d["engines"], list(d["engines"])
sweep = {row["devices"]: row for row in d["batch_axis_scaling"]}
assert set(sweep) == {1, 2, 4}, sweep
for n_dev, row in sweep.items():
    assert row["throughput_rps"] > 0, (n_dev, row)
for name, r in d["engines"].items():
    lat = r["latency_ms"]
    assert {"p50", "p90", "p95", "p99"} <= set(lat), (name, lat)
    assert r["throughput_rps"] > 0, (name, r)
    # compile keys are strings by contract (docs/BENCH_SCHEMAS.md)
    assert all(isinstance(k, str) for k in r["compile_s_per_bucket"]), r
# §10: the open-loop sync-vs-async sustained_load section is well-formed —
# offered/achieved/rejections per step, a knee identified, and the AOT
# hot-loop invariant held (zero compilations, zero misses in the timed loop)
sl = d["sustained_load"]
assert set(sl["engines"]) == set(d["engines"]), sl.keys()
for name, r in sl["engines"].items():
    assert r["open_loop"] and r["steps"], (name, r)
    for s in r["steps"]:
        assert {"offered_rps", "achieved_rps", "rejection_rate",
                "latency_ms"} <= set(s), (name, s)
    assert r["knee"]["index"] in range(len(r["steps"])), (name, r["knee"])
    assert r["knee"]["criterion"], (name, r["knee"])
    assert r["sync_baseline"]["achieved_rps"] > 0, (name, r)
    assert r["aot"]["hot_loop_compiles"] == 0, (name, r["aot"])
    assert r["aot"]["misses"] == 0, (name, r["aot"])
    assert isinstance(r["knee_exceeds_sync"], bool), (name, r)
print("BENCH_tm_serve.json well-formed:", ", ".join(d["engines"]),
      "| scaling devices:", sorted(sweep),
      "| backend:", d["topology"]["backend"],
      "| sustained knees:", {n: r["knee"]["achieved_rps"]
                             for n, r in sl["engines"].items()})
EOF

echo "== dryrun --tm (backend routes + vote all-reduce) =="
python -m repro.launch.dryrun --tm
python - <<'EOF'
import json
# even cell (PR 3/4 contract) + the previously-indivisible ragged cell (§9)
for mesh, rule in (("2x4", "composed_even"), ("2x3", "composed_ragged")):
    d = json.load(open(f"results/dryrun/tm/{mesh}.json"))
    assert not d["failures"], d["failures"]
    routes = d["backend_routes"]
    # the Pallas route must actually run the kernel shard-locally, with the
    # (B, m) vote all-reduce still the only collective (DESIGN.md §8)
    pi = routes["pallas_interpret"]
    assert pi["pallas_call_in_jaxpr"] and pi["one_vote_all_reduce"], pi
    assert not routes["xla"]["pallas_call_in_jaxpr"], routes["xla"]
    # the indexed engine's matmul-form Eq. 4 routes the same way
    # (indexed_votes primitive: pallas_call ⇔ pallas backend, the one vote
    # all-reduce unchanged), and its train leg covers index_update — the
    # batched replay keeps the step all-reduce-only on both backends (§11)
    ipi = routes["indexed_pallas_interpret"]
    assert ipi["pallas_call_in_jaxpr"] and ipi["one_vote_all_reduce"], ipi
    assert ipi["train_step_all_reduce_only"], ipi
    ix = routes["indexed_xla"]
    assert not ix["pallas_call_in_jaxpr"], ix
    assert ix["one_vote_all_reduce"] and ix["train_step_all_reduce_only"], ix
    # the route record names which composition rule fired (§9)
    seq = d["train_step_sequential"]
    assert seq["composition"] == rule and seq["all_reduce_only"], seq
    print(f"dryrun --tm {mesh} OK: composition={seq['composition']},",
          {k: v["pallas_call_in_jaxpr"] for k, v in routes.items()})
EOF

echo "CI smoke: OK"
