"""benchmarks.common: the suite's warmup + median-of-k timing harness."""
from benchmarks.common import Measurement, measure, time_fn


def test_measure_median_of_k():
    calls = []
    m = measure(lambda: calls.append(1), warmup=2, k=5)
    assert isinstance(m, Measurement)
    assert len(calls) == 7  # warmup included
    assert m.k == 5 and len(m.times_us) == 5
    assert m.times_us == tuple(sorted(m.times_us))
    assert m.median_us == m.times_us[2]
    assert m.spread_us >= 0.0


def test_time_fn_wraps_measure():
    assert time_fn(lambda: 1 + 1, iters=2) > 0.0
