"""The trace reduction: interval arithmetic by hand, a small trace in XSpace
text form laid out as the profiler lays out a TPU run
(``data/small_trace.pbtxt``) reduced to hand-computed results, and a trace
recorded on a TPU v5e by ``probe_trace.py`` (``data/probe.xplane.pb``)."""
import pathlib

import pytest

import tracing

DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_union_length_merges_overlaps():
    assert tracing.union_length([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == 4


def test_gaps_inside_the_window():
    assert tracing.gaps([(1, 2), (1.5, 3), (4, 5)], 0, 6) == \
        [(0, 1), (3, 4), (5, 6)]
    assert tracing.gaps([(-1, 7)], 0, 6) == []


def test_labels_pick_the_covering_span():
    lab = tracing.Labels([("window", 0, 100), ("step", 10, 20),
                          ("batch", 20, 30)])
    assert lab(15) == "step" and lab(25) == "batch" and lab(50) == "host"


def reduce_text(path, spans):
    from jax.profiler import ProfileData

    return tracing.reduce(ProfileData.from_text_proto(path.read_text()), spans)


def test_small_trace_by_hand():
    """data/small_trace.pbtxt: window 1–11 µs; device ops 2–4 (fusion),
    5–6 (paged attention), 5.5–8 (qmm), 10.5–12 (fusion, clipped at 11);
    host spans work 1.5–4.6 and batch 8.5–10."""
    r = reduce_text(DATA / "small_trace.pbtxt", {"work", "batch"})
    us = 1e-6
    assert r["window_s"] == pytest.approx(10 * us)
    assert r["busy_s"] == pytest.approx(5.5 * us)
    assert r["kernels"] == pytest.approx({"paged_decode_attn": 1 * us,
                                          "qmm": 2.5 * us})
    ops = dict(r["breakdown"]["device_ops"])
    assert ops == pytest.approx({
        "fusion.1": 2 * us, "qmm.4": 2.5 * us,
        "paged_decode_attn.3": 1 * us, "fusion.2": 0.5 * us})
    assert dict(r["breakdown"]["idle_gaps"]) == pytest.approx(
        {"work": 2 * us, "batch": 2.5 * us})


def test_recorded_tpu_trace():
    """probe_trace.py's window: three rounds of one call to each of the
    benchmark's kernels (qmm; paged attention; both quantized-AdamW passes),
    each round in a ``work`` span. Every kernel is found by its op name, and
    its device time lies inside the busy time."""
    r = tracing.reduce_file(DATA / "probe.xplane.pb", {"work"})
    assert r["devices"] == 1
    assert set(r["kernels"]) == {"qmm", "paged_decode_attn",
                                 "qadamw_absmax", "qadamw_update"}
    assert 0 < sum(r["kernels"].values()) <= r["busy_s"] < r["window_s"]
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["qmm.1"] == pytest.approx(r["kernels"]["qmm"])
    # the host waits on the device inside its own spans
    assert [k for k, _ in r["breakdown"]["idle_gaps"]] == ["work"]
