"""The trace reduction, on hand-made events and on a trace recorded on the
CPU (``testdata/cpu_window.xplane.pb``; re-record with
``JAX_PLATFORMS=cpu python benchmarks/chip/test_bench_chip_trace.py``)."""

from pathlib import Path

import pytest

import trace_reduce

RECORDED = Path(__file__).resolve().parent / "testdata" / "cpu_window.xplane.pb"
PAUSE_S = 0.04
MS = 1_000_000


def test_union_merges_overlapping_and_touching_intervals():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == [
        (0, 4), (5, 7), (9, 9)]


def test_busy_idle_and_gaps_from_hand_made_events():
    # window 0..100 ms; device busy 10..30 and 25..40 (overlap) and 70..80;
    # a collective 70..75; host: dispatch 0..12, wait 12..45, pause 50..90
    ops = {"/device:TPU:0": [[(10 * MS, 30 * MS, "fusion.1"),
                              (70 * MS, 73 * MS, "all-to-all.3"),
                              (73 * MS, 75 * MS, "all_to_all.31"),
                              (75 * MS, 80 * MS, "fusion.1"),
                              (120 * MS, 130 * MS, "fusion.1")],  # outside
                             [(25 * MS, 40 * MS, "sort.2")]]}
    spans = [(0, 100 * MS, "bench.window"), (0, 12 * MS, "bench.dispatch"),
             (12 * MS, 45 * MS, "bench.wait"), (50 * MS, 90 * MS, "bench.pause"),
             (0, 500 * MS, "other")]
    r = trace_reduce.reduce_events(ops, spans)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.040)          # 10..40 and 70..80
    assert r["idle_share"] == pytest.approx(0.6)
    assert r["collective_s"] == pytest.approx(0.005)
    assert r["top_ops"][0] == ["fusion.1", pytest.approx(0.025)]
    gaps = dict((k, v) for k, v in r["idle_gaps"])
    # 0..10 -> dispatch; 40..70 -> midpoint 55 in pause; 80..100 -> 90: pause
    assert gaps == {"bench.dispatch": pytest.approx(0.010),
                    "bench.pause": pytest.approx(0.050)}


def test_self_time_leaves_out_nested_ops():
    ops = [(0, 100, "while.1"), (10, 30, "fusion.2"), (40, 60, "sort.3"),
           (45, 50, "fusion.4"), (200, 210, "copy.5")]
    assert dict(trace_reduce.self_times(ops)) == {
        "while.1": 60, "fusion.2": 20, "sort.3": 15, "fusion.4": 5,
        "copy.5": 10}
    assert trace_reduce.op_name(
        "%fusion.3 = s32[16]{0} fusion(s32[16]{0} %a), kind=kLoop") == "fusion.3"


def test_busy_is_averaged_over_devices():
    ops = {"/device:TPU:0": [[(0, 50 * MS, "a")]],
           "/device:TPU:1": [[(0, 30 * MS, "a")]]}
    r = trace_reduce.reduce_events(ops, [(0, 100 * MS, "bench.window")])
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(0.040)
    assert r["idle_gaps"] == [["bench.window", pytest.approx(0.060)]]


def test_recorded_cpu_trace():
    r = trace_reduce.reduce(RECORDED, host_ops=True)
    assert r["devices"] == 1
    assert PAUSE_S < r["window_s"] < 1.0
    assert 0 < r["busy_s"] < r["window_s"] - PAUSE_S
    assert r["idle_share"] == pytest.approx(1 - r["busy_s"] / r["window_s"])
    gaps = dict((k, v) for k, v in r["idle_gaps"])
    assert r["idle_gaps"][0][0] == "bench.pause"
    assert PAUSE_S * 0.95 < gaps["bench.pause"] <= r["window_s"]
    assert r["top_ops"] and all(s > 0 for _, s in r["top_ops"])


def test_a_trace_without_device_ops_or_window_is_an_error():
    # the CPU trace has no device plane: only a caller that asks takes its
    # host events; a run on a chip never does
    with pytest.raises(ValueError, match="no device plane"):
        trace_reduce.reduce(RECORDED)
    with pytest.raises(ValueError, match="no host span bench.window"):
        trace_reduce.reduce_events({"/device:TPU:0": [[(0, 5, "a")]]},
                                   [(0, 9, "bench.wait")])


def _record(out: Path) -> None:
    import shutil
    import tempfile
    import time

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sort(x * 3 + 1))
    x = jnp.arange(1 << 16, dtype=jnp.uint32)
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp, profiler_options=trace_reduce.options())
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(2):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                y = f(x)
            with jax.profiler.TraceAnnotation("bench.wait"):
                y.block_until_ready()
            if i == 0:
                with jax.profiler.TraceAnnotation("bench.pause"):
                    time.sleep(PAUSE_S)
    jax.profiler.stop_trace()
    out.parent.mkdir(exist_ok=True)
    shutil.copy(trace_reduce.find(tmp), out)
    shutil.rmtree(tmp)


if __name__ == "__main__":
    _record(RECORDED)
    print(trace_reduce.reduce(RECORDED, host_ops=True))
