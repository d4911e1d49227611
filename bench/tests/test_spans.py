"""The program's spans read from a trace, and the rule that names the
chip's idle gaps by them."""
import lzma
from pathlib import Path

import pytest

import spans
import trace_reduce as tr

TESTDATA = Path(tr.__file__).resolve().parent / "testdata"
MS = 1_000_000
SERVING, PLANNER = ("/host:CPU", 0), ("/host:CPU", 1)


def _sp(name, line, a, b, **meta):
    return (name, line, a * MS, b * MS, meta)


#: a window of 100 ms: the client waits in it; the chip runs [0, 10] and
#: [60, 70]; the serving thread waits on the plan over [10, 55] and
#: drains over [70, 100]; a planner orders rows over [5, 40] and builds
#: tiles over [40, 50] of one level, under one request
OPS = {0: [("%fusion.1 = f32[8]{0} fusion()", 0, 10 * MS),
           ("%fusion.2 = f32[8]{0} fusion()", 60 * MS, 70 * MS)]}
BENCH = [("bench.submit", 0, 1 * MS), ("bench.wait", 1 * MS, 100 * MS)]
SPANS = [_sp("serve.plan_wait", SERVING, 10, 55, wave=1),
         _sp("serve.dispatch", SERVING, 55, 62, wave=1),
         _sp("serve.upload", SERVING, 56, 58, wave=1),
         _sp("serve.drain", SERVING, 70, 100, wave=1),
         _sp("plan.request", PLANNER, 2, 52, rid=7, wave=1, cpu_ms=25.0),
         _sp("plan.level", PLANNER, 4, 51, rid=7, wave=1, level=0),
         _sp("plan.order", PLANNER, 5, 40, rid=7, wave=1),
         _sp("plan.tiles", PLANNER, 40, 50, rid=7, wave=1)]


def test_split_name_reads_folded_metadata():
    assert spans.split_name("plan.level#level=3,rid=7#") == (
        "plan.level", {"level": "3", "rid": "7"})
    assert spans.split_name("serve.drain") == ("serve.drain", {})


def test_leaves_are_the_innermost_spans():
    names = sorted(sp[0] for sp in spans.leaves(SPANS))
    assert names == ["plan.order", "plan.tiles", "serve.drain",
                     "serve.plan_wait", "serve.upload"]


@pytest.mark.parametrize("gap,want", [
    # the planner ordered rows for most of it
    ((10, 55), "serve.plan_wait>plan.order"),
    # only the tiles ran then
    ((42, 50), "serve.plan_wait>plan.tiles"),
    # no planner span: the wait alone
    ((53, 55), "serve.plan_wait"),
    ((75, 90), "serve.drain"),
])
def test_a_gap_is_named_by_the_serving_span_and_plan_phase(gap, want):
    g = (gap[0] * MS, gap[1] * MS)
    assert spans.label(g, SPANS, BENCH) == want


def test_without_program_spans_a_gap_keeps_the_bench_label():
    assert spans.label((20 * MS, 30 * MS), [], BENCH) == "bench.wait"
    assert spans.label((20 * MS, 30 * MS), [], []) == "no bench span"


def test_reduce_events_by_hand():
    r = spans.reduce_events(OPS, BENCH, SPANS)
    # idle [10, 60] and [70, 100]: 45 ms of the 80 under the plan wait
    assert r["idle_gaps"] == [["serve.plan_wait>plan.order",
                               pytest.approx(0.050)],
                              ["serve.drain", pytest.approx(0.030)]]
    assert r["idle_plan_wait_pct"] == pytest.approx(100.0 * 45 / 80)
    assert r["requests"] == 1
    assert r["plan_request_ms"] == pytest.approx(50.0)
    assert r["plan_cpu_pct"] == pytest.approx(50.0)
    assert r["plan_ms"] == {"plan.level": {"0": pytest.approx(47.0)},
                            "plan.order": {"0": pytest.approx(35.0)},
                            "plan.tiles": {"0": pytest.approx(10.0)}}


def test_a_recorded_trace_without_program_spans(tmp_path):
    """The chip trace of ``bench/testdata`` (``test_trace_reduce``) was
    recorded before the program had spans: every gap keeps the label and
    the length that ``trace_reduce`` gives it."""
    path = tmp_path / "window.xplane.pb"
    path.write_bytes(lzma.decompress(
        (TESTDATA / "m16.stream.xplane.pb.xz").read_bytes()))
    assert spans.program_spans(tr.load(str(path))) == []
    r = spans.reduce_file(str(path))
    want = tr.reduce_file(str(path), n_chips=1)["breakdown"]["idle_gaps"]
    assert [n for n, _ in r["idle_gaps"]] == [n for n, _ in want]
    assert [d for _, d in r["idle_gaps"]] == pytest.approx(
        [d for _, d in want])
    assert r["idle_plan_wait_pct"] is None
    assert "plan_ms" not in r
