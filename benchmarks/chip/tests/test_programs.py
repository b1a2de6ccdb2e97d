"""The reduction by the program's own names (programs.py), its metric
readers, and trace_programs.py's record: a small hand-built trace
(``data/programs_trace.pbtxt``) reduced to hand-computed results, a trace
recorded on a TPU v5e by ``probe_programs.py`` (``data/programs.xplane.pb``),
and synthetic records."""
import pathlib

import numpy as np
import pytest

import core
import programs
import trace_programs

DATA = pathlib.Path(__file__).resolve().parent / "data"
US = 1e-6


def metric(name):
    return core.load_module(core.HERE / "metrics" / f"{name}.py")


@pytest.fixture(scope="module")
def small():
    from google.protobuf import text_format

    space = text_format.Parse((DATA / "programs_trace.pbtxt").read_text(),
                              programs.xplane_pb2().XSpace())
    return programs.reduce(space, {"ServeEngine.step"})


def test_names():
    assert programs.module_name("jit_serve_decode(-5780957)") == \
        "serve_decode"
    assert programs.module_name("jit__lambda(12)") == "_lambda"
    assert programs.scope_of("jit(train_step)/grad_channel/mul") == \
        "grad_channel"
    assert programs.scope_of(
        "jit(train_step)/forward_backward/transpose(jvp(dot))") == \
        "forward_backward"
    assert programs.scope_of("jit(serve_decode)/while/body/add") == \
        "unscoped"
    assert programs.scope_of(None) == "unscoped"


def test_innermost_labels():
    lab = programs.Innermost([
        ("window", 0, 100), ("ServeEngine.step", 10, 60),
        ("serve.step", 11, 59), ("serve.admit", 12, 20),
        ("serve.page_checksum", 14, 16), ("serve.decode.wait", 30, 50),
        ("next_batch", 70, 80)])
    assert lab(15) == "serve.page_checksum"
    assert lab(18) == "serve.admit"
    assert lab(25) == "serve.step"            # after a child closed
    assert lab(40) == "serve.decode.wait"
    assert lab(59.5) == "ServeEngine.step"
    assert lab(75) == "next_batch"
    assert lab(65) == "host" and lab(5) == "host"


def test_small_trace_programs(small):
    """Module events clipped to the window; a run counts where its middle
    lies inside (the last decode run's does not)."""
    assert small["window_s"] == pytest.approx(10 * US)
    p = small["programs"]
    assert set(p) == {"serve_chunk", "serve_decode", "train_step"}
    assert p["serve_chunk"]["seconds"] == pytest.approx(2 * US)
    assert p["serve_decode"]["seconds"] == pytest.approx(3.5 * US)
    assert p["train_step"]["seconds"] == pytest.approx(1.5 * US)
    assert [p[k]["runs"] for k in sorted(p)] == [1, 1, 1]


def test_small_trace_scopes(small):
    """Ops inside a run, by the first scope on their tf_op path; the loop's
    own event is left out; op time never exceeds its program's."""
    sc = small["scopes"]
    assert sc["serve_chunk"] == pytest.approx({"unscoped": 2 * US})
    assert sc["serve_decode"] == pytest.approx({"unscoped": 3.5 * US})
    assert sc["train_step"] == pytest.approx(
        {"grad_channel": 0.5 * US, "forward_backward": 1 * US})
    for fn, by_scope in sc.items():
        assert sum(by_scope.values()) <= \
            small["programs"][fn]["seconds"] * (1 + 1e-9)


def test_small_trace_spans_and_idle(small):
    sp = small["program_spans"]
    assert sp["serve.step"]["count"] == 1
    assert sp["serve.step"]["seconds"] == pytest.approx(7 * US)
    assert sp["serve.decode.wait"]["seconds"] == pytest.approx(3.1 * US)
    assert "ServeEngine.step" not in sp
    assert dict(small["idle_gaps"]) == pytest.approx({
        "serve.prefill_chunk": 1 * US, "serve.first_token": 1 * US,
        "serve.commit": 0.5 * US, "data.next_batch": 0.5 * US})


def test_recorded_tpu_trace():
    """probe_programs.py's window on a TPU v5e: a chunked engine with the
    prefix cache and two 8-bit train steps. Each program is found by its
    name, the train step's ops by scope, and the program's spans."""
    r = programs.reduce_file(DATA / "programs.xplane.pb", set())
    p = r["programs"]
    assert {"serve_decode", "serve_chunk", "serve_sample_first",
            "train_step"} <= set(p)
    assert p["train_step"]["runs"] == 2
    sc = r["scopes"]["train_step"]
    assert sc["grad_channel"] > 0 and sc["forward_backward"] > 0
    assert sc["optimizer"] > 0
    assert sum(sc.values()) <= p["train_step"]["seconds"]
    assert {"serve.step", "serve.admit", "serve.prefill_chunk",
            "serve.first_token", "serve.decode.dispatch",
            "serve.decode.wait", "serve.commit", "serve.page_checksum",
            "train.step", "data.next_batch"} <= set(r["program_spans"])


# ----------------------------------------------------------------- readers --
def rec_serve():
    return {"serve": {"decode_steps": 4, "token_gaps": [0.1] * 19 + [0.5]},
            "trace": {"programs": {
                "serve_decode": {"seconds": 1.2, "runs": 4},
                "serve_chunk": {"seconds": 0.15, "runs": 3}},
                "program_spans": {
                    "serve.step": {"count": 4, "seconds": 1.6},
                    "serve.decode.wait": {"count": 4, "seconds": 1.2},
                    "serve.first_token": {"count": 2, "seconds": 0.08}}}}


def rec_train():
    return {"train": {"steps": 5},
            "trace": {"scopes": {"train_step": {
                "grad_channel": 0.1, "forward_backward": 6.0}}}}


def test_readers_on_synthetic_records():
    s, t = rec_serve(), rec_train()
    assert metric("decode_device_ms.serve").read(s) == pytest.approx(300)
    assert metric("chunk_device_ms.serve").read(s) == pytest.approx(50)
    assert metric("sched_ms.serve").read(s) == pytest.approx(80)
    assert metric("itl_p95_ms.serve").read(s) == pytest.approx(
        1e3 * np.percentile([0.1] * 19 + [0.5], 95))
    assert metric("grad_channel_ms.train").read(t) == pytest.approx(20)


@pytest.mark.parametrize("name", trace_programs.PROGRAM_METRICS)
def test_readers_find_nothing_without_the_programs_names(name):
    """A record as run.py builds it (no program spans, programs, scopes or
    token gaps) reads nothing, and nothing is raised."""
    bare = {"serve": {"decode_steps": 4, "decode_times": [0.3]},
            "train": {"steps": 5, "next_batch_s": [0.001]},
            "trace": {"busy_s": 1.0, "window_s": 1.0, "kernels": {}}}
    assert metric(name).read(bare) is None


def test_token_gaps_inside_the_window():
    class F:
        def __init__(self, times):
            self.token_times = np.asarray(times)

    done = [F([1.0, 2.0, 4.0]),          # the first gap starts before
            F([2.0, 2.5, 3.5]),
            F([4.0, 6.0]),               # finished after the window
            F([])]
    assert trace_programs.token_gaps(done, 1.5, 5.0) == [2.0, 0.5, 1.0]


def test_program_record_adds_only_what_readers_need():
    red = {"busy_s": 1.0, "window_s": 2.0, "kernels": {"qmm": 0.1}}
    pred = {"programs": {"a": 1}, "scopes": {"b": 2},
            "program_spans": {"c": 3}, "idle_gaps": [], "window_s": 2.0}
    rec = trace_programs.program_record({"serve": {"x": 1}}, red, pred,
                                        [0.1])
    assert rec["trace"] == {**red, "programs": {"a": 1}, "scopes": {"b": 2},
                            "program_spans": {"c": 3}}
    assert rec["serve"] == {"x": 1, "token_gaps": [0.1]}
    assert "token_gaps" not in trace_programs.program_record(
        {"train": {}}, red, pred, None).get("train")
