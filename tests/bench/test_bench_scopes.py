"""Device time per named scope and idle gaps named by the program's spans
(``bench/scopes.py``), on a synthetic trace whose numbers are known and on
the trace recorded on a v5e; the per-batch readings built on them; and the
planner's padded-row share, read from the session's counters."""
import gzip
import os
from collections import namedtuple

import pytest

from bench_util import ONE, ROOT, run_child

from bench import scopes, spec, xplane

RECORDED = os.path.join(ROOT, "bench", "testdata", "tiny_rw95.xplane.pb.gz")

# window 1,000-11,000 ns.  Host: bench.session_run 2,000-9,000 >
# repro.session.run 2,100-8,900 > repro.session.inputs 2,400-3,300.
# Device: jit_other 1,200-2,400 (one op, unscoped); the dist program
# 3,300-8,500: a while (no tf_op) 3,300-6,500 around two local_stage ops
# 3,400-5,000 and 5,200-6,000, a gather op 6,500-7,000, the combine's
# kernel 7,000-8,000 (tf_op by reference), and an XLA gather outside any
# scope 8,000-8,500.
SYNTHETIC = '''
planes { id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 7000000 }
    events { metadata_id: 3 offset_ps: 1100000 duration_ps: 6800000 }
    events { metadata_id: 4 offset_ps: 1400000 duration_ps: 900000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.session_run" } }
  event_metadata { key: 3 value { id: 3 name: "repro.session.run" } }
  event_metadata { key: 4 value { id: 4 name: "repro.session.inputs" } } }
planes { id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 200000 duration_ps: 1200000 }
    events { metadata_id: 11 offset_ps: 2300000 duration_ps: 5200000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 200000 duration_ps: 1200000 }
    events { metadata_id: 2 offset_ps: 2300000 duration_ps: 3200000 }
    events { metadata_id: 3 offset_ps: 2400000 duration_ps: 1600000 }
    events { metadata_id: 3 offset_ps: 4200000 duration_ps: 800000 }
    events { metadata_id: 4 offset_ps: 5500000 duration_ps: 500000 }
    events { metadata_id: 5 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 6 offset_ps: 7000000 duration_ps: 500000 } }
  event_metadata { key: 1 value { id: 1 name: "multiply.1"
    stats { metadata_id: 9 str_value: "jit(other)/mul:" } } }
  event_metadata { key: 2 value { id: 2 name: "while.7" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.3"
    stats { metadata_id: 9 str_value:
      "jit(_batch_dist_kernel)/local_stage/vmap(jit(single_source_dist))/while/body/add:" } } }
  event_metadata { key: 4 value { id: 4 name: "gather.2"
    stats { metadata_id: 9 str_value:
      "jit(_batch_dist_kernel)/gather/jit(take_along_axis)/gather:" } } }
  event_metadata { key: 5 value { id: 5 name: "tropical_matmul.1"
    stats { metadata_id: 9 ref_value: 12 } } }
  event_metadata { key: 6 value { id: 6 name: "gather.9"
    stats { metadata_id: 9 str_value: "jit(_batch_dist_kernel)/jit(_take)/gather:" } } }
  event_metadata { key: 10 value { id: 10 name: "jit_other(1)" } }
  event_metadata { key: 11 value { id: 11 name: "jit__batch_dist_kernel(2)" } }
  stat_metadata { key: 9 value { id: 9 name: "tf_op" } }
  stat_metadata { key: 12 value { id: 12 name:
    "jit(_batch_dist_kernel)/combine/jit(tropical_matmul)/tropical_matmul/pallas_call:" } } }
'''


def _write(tmp_path_factory, text):
    from jax.profiler import ProfileData
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


@pytest.fixture(scope="module")
def synthetic(tmp_path_factory):
    path = _write(tmp_path_factory, SYNTHETIC)
    return scopes.reduce_scopes(path, chips=1), xplane.reduce_trace(path, 1)


def test_scopes_split_busy_time_by_innermost_op(synthetic):
    red, _ = synthetic
    us = pytest.approx
    assert red["busy_s"] == us(6.4e-6)
    # the while's own time between its body's ops is unscoped, as are the
    # other program's op and the XLA gather outside any scope
    assert red["scopes_s"] == {"unscoped": us(2.5e-6),
                               "local_stage": us(2.4e-6),
                               "gather": us(0.5e-6), "combine": us(1e-6)}
    assert sum(red["scopes_s"].values()) == us(red["busy_s"])
    assert red["program_scopes_s"] == {
        "jit_other": {"unscoped": us(1.2e-6)},
        "jit__batch_dist_kernel": {"unscoped": us(1.3e-6),
                                   "local_stage": us(2.4e-6),
                                   "gather": us(0.5e-6),
                                   "combine": us(1e-6)}}


def test_gap_named_by_innermost_program_span(synthetic):
    red, old = synthetic
    assert sorted(red["gaps"], key=lambda g: -g[1]) == [
        ("bench.session_run", pytest.approx(2.5e-6)),
        ("repro.session.inputs", pytest.approx(0.9e-6)),
        ("host idle", pytest.approx(0.2e-6))]
    # the same numbers as bench/xplane.py, which names gaps by bench.* only
    for key in ("window_s", "busy_s", "devices", "programs_s"):
        assert red[key] == old[key], key
    assert sorted(g for _, g in red["gaps"]) == \
        sorted(g for _, g in old["gaps"])


def test_scope_of_reads_enclosing_scopes_only():
    assert scopes.scope_of(None) == "unscoped"
    assert scopes.scope_of("jit(f)/combine/jit(g)/gather/add:") == "combine"
    assert scopes.scope_of("jit(f)/vmap(jit(h))/while/body/gather:") == \
        "unscoped"
    assert scopes.scope_of("jit(f)/shard_map/collective/psum:") == \
        "collective"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("rec") / "t.xplane.pb"
    with gzip.open(RECORDED) as f:
        path.write_bytes(f.read())
    return (scopes.reduce_scopes(RECORDED, chips=1),
            xplane.reduce_trace(str(path), chips=1))


def test_recorded_trace_reads_as_before(recorded):
    """The v5e trace recorded before the program had scopes or spans: the
    reduction's window, busy time, programs and gaps are bench/xplane.py's
    to the nanosecond, and all of its device time is unscoped."""
    red, old = recorded
    for key in old:
        assert red[key] == old[key], key
    assert red["scopes_s"] == {"unscoped": pytest.approx(red["busy_s"])}
    for prog in ("jit__batch_reach_kernel", "jit__batch_dist_kernel"):
        assert set(red["program_scopes_s"][prog]) == {"unscoped"}


Span = namedtuple("Span", "name t0 t1 span_id parent_id batch_id attrs")


def test_per_batch_readings():
    spans = [
        Span("repro.serve.batch", 10.0, 12.0, 1, None, 1, {}),
        Span("repro.session.run", 10.1, 11.9, 2, 1, 1, {}),
        Span("repro.session.device", 10.2, 11.2, 3, 2, 1, {}),
        Span("repro.session.device", 11.3, 11.8, 4, 2, 1, {}),
        Span("repro.serve.batch", 12.0, 13.0, 5, None, 5, {}),
        Span("repro.session.run", 12.1, 12.9, 6, 5, 5, {}),
        Span("repro.session.device", 12.2, 12.8, 7, 6, 5, {}),
        # begun after the window: not read
        Span("repro.serve.batch", 20.0, 21.0, 8, None, 8, {}),
        Span("repro.session.run", 20.1, 20.9, 9, 8, 8, {}),
    ]
    red = {"scopes_s": {"local_stage": 0.8, "combine": 0.2}}
    assert scopes.host_batch_ms(spans, 10.0, 13.0) == \
        pytest.approx(1e3 * ((2.0 - 1.5) + (1.0 - 0.6)) / 2)
    assert scopes.scope_ms_per_run(red, spans, 10.0, 13.0,
                                   "local_stage") == pytest.approx(400.0)
    assert scopes.scope_ms_per_run(red, spans, 10.0, 13.0,
                                   "gather") == 0.0
    # a run of a program without scopes or spans reads nothing
    assert scopes.scope_ms_per_run({"scopes_s": {}}, spans, 10.0, 13.0,
                                   "combine") is None
    assert scopes.scope_ms_per_run(red, [], 10.0, 13.0, "combine") is None
    assert scopes.host_batch_ms([], 10.0, 13.0) is None


def test_padded_row_share_reads_the_session_counters():
    read = spec.Spec(ROOT).reader("padded_row_share")
    assert read({"stats": {"rows_useful": 48, "rows_padded": 64}}) == 25.0
    # the parent program's session has no such counters
    assert read({"stats": {"queries": 64, "batches": 1}}) is None


def test_traced_run_reports_padded_row_share(tmp_path):
    info, result = run_child(ONE, tmp_path, trace=1)
    assert result["correct"] is True, result["checks"]
    share = result["metrics"]["padded_row_share"]["value"]
    stats = info["session_stats"]
    assert share == pytest.approx(100.0 * (
        stats["rows_padded"] - stats["rows_useful"]) / stats["rows_padded"])
    assert 0.0 <= share < 100.0


SCOPED = os.path.join(ROOT, "bench", "testdata",
                      "tiny_closed_scoped.xplane.pb.gz")


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    """A one-second window of ``er16x16k.reachdist_closed`` at the tests'
    tiny size, traced on one v5e with the program's scopes and spans on;
    kept with only the first chip's programs and ops (each op's tf_op) and
    the host's bench.* and repro.* spans."""
    path = tmp_path_factory.mktemp("scoped") / "t.xplane.pb"
    with gzip.open(SCOPED) as f:
        path.write_bytes(f.read())
    return (scopes.reduce_scopes(SCOPED, chips=1),
            xplane.reduce_trace(str(path), chips=1))


def test_scoped_trace_splits_both_batch_programs(scoped):
    red, old = scoped
    for key in ("window_s", "busy_s", "devices", "programs_s"):
        assert red[key] == old[key], key
    assert sum(red["scopes_s"].values()) == pytest.approx(red["busy_s"])
    for prog in ("jit__batch_reach_kernel", "jit__batch_dist_kernel"):
        split = red["program_scopes_s"][prog]
        assert split["local_stage"] > 0 and split["combine"] > 0, split
        named = sum(split.get(s, 0.0)
                    for s in ("local_stage", "gather", "combine"))
        assert named >= 0.95 * sum(split.values()), split


def test_scoped_trace_gaps_name_program_spans(scoped):
    red, old = scoped
    # the same gaps; each now put down to a span of the program's own
    assert [g for _, g in red["gaps"]] == [g for _, g in old["gaps"]]
    assert {n for n, _ in old["gaps"]} == {"bench.session_run"}
    assert all(n.startswith("repro.") for n, _ in red["gaps"]), red["gaps"]
