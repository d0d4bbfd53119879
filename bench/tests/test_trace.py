"""The reduction from a profiler trace to busy time and the breakdown."""
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench.trace import SPANS, load, reduce_trace

DATA = Path(__file__).resolve().parent / "data"

# device ops: a loop at [1, 4] microseconds with a fusion nested at [2, 3],
# and a fusion at [7, 8]; host spans bench.step [1, 4.5], bench.wait
# [4.5, 9]
SYNTHETIC = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "%while.1 = (s32[]) while()" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[8] fusion()" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = f32[8] fusion()" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3500000 }
    events { metadata_id: 2 offset_ps: 3500000 duration_ps: 4500000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.step" } }
  event_metadata { key: 2 value { id: 2 name: "bench.wait" } } }
'''


def test_busy_is_the_union_of_device_ops():
    r = reduce_trace(ProfileData.from_text_proto(SYNTHETIC))
    assert r["window_s"] == pytest.approx(8e-6)
    assert r["busy_s"] == pytest.approx(4e-6)        # [1, 4] and [7, 8]
    # self time: the loop's 3 us less the 1 us nested in it
    ops = {name: t for name, t in r["device_ops"]}
    assert ops == {"while.1": pytest.approx(2e-6),
                   "fusion.2": pytest.approx(1e-6),
                   "fusion.3": pytest.approx(1e-6)}
    # gaps [4, 7] and [8, 9], both while the host waited for an arrival
    assert r["idle_gaps"] == [["bench.wait", pytest.approx(3e-6)],
                              ["bench.wait", pytest.approx(1e-6)]]


def test_a_trace_with_no_device_reads_nothing():
    host_only = SYNTHETIC[SYNTHETIC.index("planes { id: 2"):]
    assert reduce_trace(ProfileData.from_text_proto(host_only)) == {}


def test_trace_recorded_on_the_chip():
    """A trace of a few steps recorded on one v5e with the benchmark's span
    names: a jitted matmul step inside bench.step, sleeps inside bench.wait
    and bench.submit."""
    r = reduce_trace(load(DATA / "v5e_steps.xplane.pb"))
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["device_ops"] and all(s > 0 for _, s in r["device_ops"])
    names = {n for n, _ in r["idle_gaps"]}
    assert names <= set(SPANS) | {"none"}
    assert "bench.wait" in names
