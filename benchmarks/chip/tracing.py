"""Reduce a profiler trace (``*.xplane.pb``) of the measured window to what
the per-layer metrics read.

* The window: the host span named ``window`` (a ``TraceAnnotation`` the
  harness puts around the measured window), on the trace's own clock.
* Device operations: the events of each TPU plane's "XLA Ops" line, clipped
  to the window. Busy time is the union of their intervals, averaged over
  the chips; the window's length is ``window_s``.
* Kernel time: the summed device time of the operations that run a Pallas
  kernel (``custom_call_target="tpu_custom_call"`` in the event's HLO text),
  keyed by the kernel's op name: the name of the jitted function that makes
  the ``pallas_call``, as XLA names the call (``%qmm.3`` → ``qmm``).
* Idle gaps: the spans between device operations, each labelled by the
  benchmark's host span that covers its middle (``host`` when none does),
  averaged over the chips.
* ``breakdown``: the ten device operations that took most time (a loop or
  a branch, whose event spans the operations of its body, left out), and the
  ten host labels that idle time fell under, with seconds.
"""
from __future__ import annotations

import bisect
import collections
import pathlib
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
PALLAS = 'custom_call_target="tpu_custom_call"'
# an event of the "XLA Ops" line is named by the op's HLO text:
# "%qmm.3 = f32[128,2048]{...} custom-call(...), custom_call_target=..."
OP_NAME = re.compile(r"^%?([^\s=]+)")
# control flow: its event spans the events of the operations it runs
CONTROL = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def op_name(ev) -> str:
    """The HLO instruction an event ran (``fusion.12``)."""
    return OP_NAME.match(ev.name).group(1)


def kernel_of(ev) -> str | None:
    """The Pallas kernel an operation runs, if any, by its op name without
    XLA's numbering (``qmm.3`` → ``qmm``)."""
    if PALLAS not in ev.name:
        return None
    return re.sub(r"\.\d+$", "", op_name(ev))


def union_length(iv: list[tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(iv):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def gaps(iv: list[tuple[float, float]], t0: float, t1: float):
    """Intervals of [t0, t1] that no interval of ``iv`` covers."""
    out, cur = [], t0
    for s, e in sorted(iv):
        if s > cur:
            out.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        out.append((cur, t1))
    return [(s, e) for s, e in out if e > s]


def host_spans(pd, names: set[str]):
    """(name, start, end) of the benchmark's host spans, in ns."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names or ev.name == "window":
                    out.append((ev.name, ev.start_ns, ev.end_ns))
    return out


class Labels:
    """The host span covering a time. The benchmark's spans follow one
    another (only ``window`` holds others), so the covering one is the last
    to start at or before it."""

    def __init__(self, spans):
        inner = sorted((s, e, n) for n, s, e in spans if n != "window")
        self.starts = [s for s, _, _ in inner]
        self.inner = inner

    def __call__(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        if i >= 0 and self.inner[i][1] >= t:
            return self.inner[i][2]
        return "host"


def reduce(pd, span_names: set[str]) -> dict:
    spans = host_spans(pd, span_names)
    label = Labels(spans)
    wins = [(s, e) for n, s, e in spans if n == "window"]
    if not wins:
        raise ValueError("the trace holds no 'window' span")
    t0, t1 = wins[0]
    ops = collections.defaultdict(float)
    kernels = collections.defaultdict(float)
    busy, idle = [], collections.defaultdict(float)
    n_dev = 0
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
        if not lines:
            continue
        n_dev += 1
        iv = []
        for ev in lines[0].events:
            s, e = max(ev.start_ns, t0), min(ev.end_ns, t1)
            if e <= s:
                continue
            iv.append((s, e))
            name = op_name(ev)
            if not CONTROL.match(name):
                ops[name] += (e - s) * 1e-9
            k = kernel_of(ev)
            if k:
                kernels[k] += (e - s) * 1e-9
        busy.append(union_length(iv) * 1e-9)
        for s, e in gaps(iv, t0, t1):
            idle[label((s + e) / 2)] += (e - s) * 1e-9
    if not n_dev:
        raise ValueError("the trace holds no device operations")
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    top_idle = sorted(((k, v / n_dev) for k, v in idle.items()),
                      key=lambda kv: -kv[1])[:10]
    return {"busy_s": sum(busy) / n_dev, "window_s": (t1 - t0) * 1e-9,
            "kernels": dict(kernels), "devices": n_dev,
            "breakdown": {"device_ops": [[k, v] for k, v in top],
                          "idle_gaps": [[k, v] for k, v in top_idle]}}


def reduce_file(path: str | pathlib.Path, span_names: set[str]) -> dict:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(str(path)), span_names)


def reduce_dir(d: pathlib.Path, span_names: set[str]) -> dict:
    paths = sorted(pathlib.Path(d).rglob("*.xplane.pb"))
    if not paths:
        raise ValueError(f"no trace under {d}")
    return reduce_file(paths[-1], span_names)
