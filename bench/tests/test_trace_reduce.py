"""The reduction from a trace's events to busy, idle, kernel and collective
time, and the breakdown."""
from pathlib import Path

import pytest

import trace_reduce as tr

TESTDATA = Path(tr.__file__).resolve().parent / "testdata"


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_reduce_events_by_hand():
    ms = 1_000_000
    kernel = ('%closed_call.3 = f32[8]{0} custom-call(f32[8]{0} %p), '
              'custom_call_target="tpu_custom_call"')
    ops = {0: [("%fusion.1 = f32[8]{0} fusion()", 0, 2 * ms),
               (kernel, 2 * ms, 4 * ms),
               ("%all-to-all.2 = f32[8]{0} all-to-all()", 6 * ms, 7 * ms),
               ("%fusion.9 = f32[8]{0} fusion()", 9 * ms, 12 * ms)],
           1: [("%fusion.1 = f32[8]{0} fusion()", 0, 10 * ms)]}
    spans = [("bench.submit", 0, 1 * ms), ("bench.wait", 1 * ms, 10 * ms)]
    r = tr.reduce_events(ops, spans, n_chips=2)
    assert r["window_s"] == pytest.approx(0.010)
    # chip 0 busy [0,4] + [6,7] + [9,10] = 6 ms; chip 1 busy 10 ms
    assert r["busy_s"] == pytest.approx((0.006 + 0.010) / 2)
    # op time inside the window, averaged over the 2 chips
    assert r["kernel_s"]["sspnna"] == pytest.approx(0.002 / 2)
    assert r["collective_s"] == pytest.approx(0.001 / 2)
    assert r["op_s"]["fusion"] == pytest.approx((0.002 + 0.001 + 0.010) / 2)
    assert r["op_s"]["closed_call[tpu_custom_call]"] == pytest.approx(
        0.002 / 2)
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["bench.wait", pytest.approx(0.002)]   # [4, 6]
    assert gaps[1] == ["bench.wait", pytest.approx(0.002)]   # [7, 9]
    assert r["breakdown"]["device_ops"][0][0] == "fusion"


def test_one_chip_of_a_trace_that_holds_more():
    ops = {0: [("a", 0, 10)], 1: [("a", 0, 5)]}
    r = tr.reduce_events(ops, [], n_chips=1)
    assert r["busy_s"] == pytest.approx(10e-9)
    assert r["window_s"] == pytest.approx(10e-9)


def test_nested_events_count_once():
    ms = 1_000_000
    ops = {0: [("%while.1 = () while()", 0, 10 * ms),
               ("%closed_call.2 = () custom-call(), custom_call_target="
                '"tpu_custom_call"', 1 * ms, 7 * ms),
               ("%fusion.3 = () fusion()", 8 * ms, 9 * ms)]}
    r = tr.reduce_events(ops, [], n_chips=1)
    assert r["busy_s"] == pytest.approx(0.010)
    assert r["kernel_s"]["sspnna"] == pytest.approx(0.006)
    assert r["op_s"] == pytest.approx({"while": 0.003, "fusion": 0.001,
                                       "closed_call[tpu_custom_call]": 0.006})


def test_a_recorded_chip_trace(tmp_path):
    """A window of ``m16.stream`` recorded on one TPU v5e (two frames, one
    wave each). By hand: the device plane's ``XLA Modules`` line holds the
    two runs of the wave program and four tiny jitted calls; the union of
    the ops they hold is what the reduction calls busy, and the window
    runs from the first ``bench.submit`` to the end of the last span.

    Recorded by a ``--trace 1`` run of ``bench/run.py`` with a short
    window, its ``.xplane.pb`` (``trace_reduce.find_trace`` of the run's
    trace directory) copied out before the run removes the directory, and
    compressed with ``xz``."""
    import lzma

    from jax.profiler import ProfileData

    path = tmp_path / "window.xplane.pb"
    path.write_bytes(lzma.decompress(
        (TESTDATA / "m16.stream.xplane.pb.xz").read_bytes()))
    r = tr.reduce_file(str(path), n_chips=1)

    pd = ProfileData.from_file(str(path))
    modules = [e for p in pd.planes if p.name == "/device:TPU:0"
               for line in p.lines if line.name == "XLA Modules"
               for e in line.events]
    waves = [e for e in modules if e.name.startswith("jit_batched_apply")]
    assert len(modules) == 6 and len(waves) == 2
    assert r["busy_s"] == pytest.approx(
        sum(e.duration_ns for e in modules) * 1e-9, abs=2e-5)
    ops, spans = tr._events(str(path))
    assert [n for n, _, _ in spans].count("bench.submit") == 2
    assert r["window_s"] == pytest.approx(5.004, abs=1e-3)
    # the fused kernel is most of each wave; fusions come next
    top = [n for n, _ in r["breakdown"]["device_ops"][:2]]
    assert top == ["closed_call[tpu_custom_call]", "fusion"]
    assert 0.5 * r["busy_s"] < r["kernel_s"]["sspnna"] < r["busy_s"]
    assert r["collective_s"] == 0.0
    # idle: before, between and after the two waves, while the host waited
    gaps = r["breakdown"]["idle_gaps"]
    assert all(name == "bench.wait" for name, _ in gaps[:3])
    assert sum(d for _, d in gaps) <= r["window_s"] - r["busy_s"] + 1e-6
    assert gaps[0][1] + gaps[1][1] > 0.8 * (r["window_s"] - r["busy_s"])
