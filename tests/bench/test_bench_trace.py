"""The trace reduction, on traces whose numbers are known: a synthetic one
written here event by event, and a small one recorded on a v5e
(``bench/testdata``)."""
import gzip
import os

import pytest

from bench_util import ROOT

from bench import xplane

RECORDED = os.path.join(ROOT, "bench", "testdata", "tiny_rw95.xplane.pb.gz")

SYNTHETIC = '''
planes { id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 3000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.session_run" } } }
planes { id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 1000000 duration_ps: 7000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 1500000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "%bool_matmul_pallas.1 = s32[256,640]{1,0} custom-call(s32[256,640]{1,0} %a, s32[640,640]{1,0} %b), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop" } }
  event_metadata { key: 3 value { id: 3 name: "%all-reduce.3 = u32[26,1]{1,0} all-reduce(u32[26,1]{1,0} %p), to_apply=%add" } }
  event_metadata { key: 4 value { id: 4 name: "jit_run(1234)" } } }
'''


@pytest.fixture(scope="module")
def synthetic(tmp_path_factory):
    from jax.profiler import ProfileData
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(SYNTHETIC))
    return xplane.reduce_trace(str(path), chips=1)


def test_busy_idle_and_collective(synthetic):
    # window 1,000-11,000 ns; ops cover 2,000-3,500 and 7,000-9,000 ns,
    # the all-reduce (7,000-9,000) busy like any other operation
    assert synthetic["window_s"] == pytest.approx(10e-6)
    assert synthetic["busy_s"] == pytest.approx(3.5e-6)
    assert synthetic["programs_s"] == {"jit_run": pytest.approx(7e-6)}


def test_idle_gaps_named_by_host_span(synthetic):
    # 3,500-7,000 overlaps bench.session_run (3,000-6,000) most
    assert sorted(synthetic["gaps"], key=lambda g: -g[1]) == [
        ("bench.session_run", pytest.approx(3.5e-6)),
        ("host idle", pytest.approx(2e-6)),
        ("host idle", pytest.approx(1e-6))]
    bd = xplane.breakdown(synthetic)
    assert bd["device_ops"] == [["jit_run", pytest.approx(7e-6)]]
    assert len(bd["idle_gaps"]) == 3


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A one-second window of an open loop of 95% reads and 5% writes on
    ``er16x16k`` at the tests' tiny size, traced on one v5e; kept with only
    its device ops, programs and the benchmark's host spans."""
    path = tmp_path_factory.mktemp("rec") / "t.xplane.pb"
    with gzip.open(RECORDED) as f:
        path.write_bytes(f.read())
    return xplane.reduce_trace(str(path), chips=1)


def test_recorded_trace_known_numbers(recorded):
    assert recorded["devices"] == 1
    assert recorded["window_s"] == pytest.approx(1.000114742)
    assert recorded["busy_s"] == pytest.approx(0.008105118)
    assert recorded["programs_s"]["jit__batch_dist_kernel"] == \
        pytest.approx(0.003588763)
    # busy is a union: no more than the programs' time, no less than the
    # longest program
    assert max(recorded["programs_s"].values()) <= recorded["busy_s"] \
        <= sum(recorded["programs_s"].values()) + 1e-9
    assert recorded["gaps"][0] == ("bench.session_run",
                                   pytest.approx(0.187133482))
