"""The trace reduction on a hand-made trace (exact numbers) and on a small
trace recorded on the CPU."""
from __future__ import annotations

import time

import pytest

from bench import trace

# window [0, 10 us]; device ops [1, 3] us and [6, 7] us (one more outside
# the window); host spans cover two of the three idle gaps
XSPACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000
             stats { metadata_id: 1 str_value: "jit_bundle_scores" } }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 19000000 duration_ps: 1000000 }
  }
  lines {
    id: 2
    name: "XLA Modules"
    timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 6000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "my_kernel" } }
  event_metadata { key: 3 value { id: 3 name: "jit_bundle_scores(7)" } }
  stat_metadata { key: 1 value { id: 1 name: "hlo_module" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "main"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 2500000 }
    events { metadata_id: 3 offset_ps: 5500000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 7000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.submit" } }
  event_metadata { key: 3 value { id: 3 name: "bench.gen_wait" } }
  event_metadata { key: 4 value { id: 4 name: "bench.wait" } }
}
"""


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData
    return trace.reduce(ProfileData.from_text_proto(XSPACE))


def test_busy_and_idle(summary):
    assert summary.window_s == pytest.approx(10e-6)
    assert summary.busy_s == pytest.approx(3e-6)
    assert summary.idle_share == pytest.approx(0.7)
    assert summary.n_devices == 1


def test_ops_and_modules_are_matched_by_name_and_stats(summary):
    assert summary.op("my_kernel") == (pytest.approx(1e-6), 1)
    assert summary.op("bundle_scores") == (pytest.approx(2e-6), 1)
    assert summary.op("no_such_kernel") is None
    assert summary.module(r"^jit_bundle_scores") == (pytest.approx(6e-6), 1)


def test_gaps_are_named_by_the_host_span_that_covers_them(summary):
    assert [(pytest.approx(s), name) for s, name in summary.gaps] == [
        (3e-6, "bench.submit"), (3e-6, "bench.wait"), (1e-6, "host:none")]
    b = summary.breakdown(k=2)
    assert b["device_ops"] == [["fusion.1", pytest.approx(2e-6)],
                               ["my_kernel", pytest.approx(1e-6)]]
    assert [name for name, _ in b["idle_gaps"]] == ["bench.submit",
                                                    "bench.wait"]


def test_a_trace_without_a_window_or_device_is_refused(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with TraceAnnotation("bench.window"):
        f(x).block_until_ready()
        time.sleep(0.001)
    jax.profiler.stop_trace()
    profile = trace.load(str(tmp_path))
    names = {ev.name for p in profile.planes if p.name.startswith("/host:")
             for ln in p.lines for ev in ln.events}
    assert "bench.window" in names
    with pytest.raises(RuntimeError, match="no operation ran"):
        trace.reduce(profile)          # the CPU is not a TPU device
    from jax.profiler import ProfileData
    no_window = XSPACE.replace('"bench.window"', '"other"')
    with pytest.raises(RuntimeError, match="bench.window"):
        trace.reduce(ProfileData.from_text_proto(no_window))
