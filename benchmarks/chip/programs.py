"""Reduce a profiler trace (``*.xplane.pb``) of the measured window by the
program's own names: its jitted programs, the named scopes inside them and
its host spans.

The program names each jitted program (the device plane's "XLA Modules" line
shows one event per run, ``jit_serve_decode(<fingerprint>)``), scopes the
phases of its train step (``SCOPES``; each op's ``tf_op`` path holds them) and
wraps its scheduler phases in host spans (``SPANS``). ``jax.profiler``'s
``ProfileData`` drops the op metadata that carries ``tf_op``, so this module
reads the raw XSpace protobuf.

* ``programs``: per program, its device seconds in the window (its module
  events clipped to the window) and its runs (events whose middle lies in
  the window), averaged over the chips.
* ``scopes``: per program, the device seconds of the operations inside its
  runs (an "XLA Ops" event inside a module event of the same chip) by the
  first of ``SCOPES`` on the op's ``tf_op`` path, ``unscoped`` where none;
  loops and branches, whose event spans the operations of their body, are
  left out.
* ``program_spans``: per name of ``SPANS``, the count and the summed
  seconds of the program's host spans inside the window.
* ``idle_gaps``: device idle time in the window by the innermost host span
  covering the gap's middle, among the benchmark's spans and the program's
  (``host`` where only the window does); the ten largest.
"""
from __future__ import annotations

import bisect
import collections
import importlib.util
import pathlib
import re

import tracing

# every host span the program opens, in one place
SPANS = ("serve.step", "serve.admit", "serve.prefill_chunk",
         "serve.first_token", "serve.decode.dispatch", "serve.decode.wait",
         "serve.commit", "serve.spec", "serve.page_checksum", "train.step",
         "data.next_batch")
SCOPES = ("sample_channel", "forward_backward", "grad_channel", "optimizer")
MODULES_LINE = "XLA Modules"
# "jit_serve_decode(5780957231092274256)" → "serve_decode"
MODULE = re.compile(r"^jit_(.+)\(-?\d+\)$")


def xplane_pb2():
    """The XSpace message module, loaded from the TensorFlow package on its
    own: it needs only protobuf, and importing TensorFlow takes seconds."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        raise ValueError("no XSpace protobuf module (tensorflow's tsl) here")
    path = (pathlib.Path(spec.submodule_search_locations[0])
            / "tsl" / "profiler" / "protobuf" / "xplane_pb2.py")
    mod_spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_file(path) -> object:
    space = xplane_pb2().XSpace()
    space.ParseFromString(pathlib.Path(path).read_bytes())
    return space


def module_name(name: str) -> str:
    """The jitted function a module event ran."""
    m = MODULE.match(name)
    return m.group(1) if m else name


def scope_of(tf_op: str | None) -> str:
    for part in (tf_op or "").split("/"):
        if part in SCOPES:
            return part
    return "unscoped"


def events(plane, line):
    """(name, start_ns, end_ns, tf_op) of a line's events."""
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
    meta = {}
    for k, md in plane.event_metadata.items():
        tf_op = None
        for st in md.stats:
            if stat_names.get(st.metadata_id) == "tf_op":
                tf_op = (st.str_value if st.HasField("str_value")
                         else stat_names.get(st.ref_value))
        meta[k] = (md.name, tf_op)
    out = []
    for ev in line.events:
        name, tf_op = meta.get(ev.metadata_id, ("", None))
        s = line.timestamp_ns + ev.offset_ps * 1e-3
        out.append((name, s, s + ev.duration_ps * 1e-3, tf_op))
    return out


def host_spans(space, names: set[str]):
    out = []
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(n, s, e) for n, s, e, _ in events(plane, line)
                    if n in names]
    return out


class Innermost:
    """The innermost host span covering a time. Spans of one thread nest,
    so the covering spans of a time are the last to start at or before it
    and its ancestors."""

    def __init__(self, spans):
        inner = sorted(((s, -e, n) for n, s, e in spans if n != "window"))
        self.starts = [s for s, _, _ in inner]
        self.ends = [-e for _, e, _ in inner]
        self.names = [n for _, _, n in inner]
        self.parent, stack = [], []
        for i, s in enumerate(self.starts):
            while stack and self.ends[stack[-1]] < s:
                stack.pop()
            self.parent.append(stack[-1] if stack else -1)
            stack.append(i)

    def __call__(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.ends[i] < t:
            i = self.parent[i]
        return self.names[i] if i >= 0 else "host"


def reduce(space, span_names: set[str]) -> dict:
    spans = host_spans(space, set(span_names) | set(SPANS) | {"window"})
    wins = [(s, e) for n, s, e in spans if n == "window"]
    if not wins:
        raise ValueError("the trace holds no 'window' span")
    t0, t1 = wins[0]
    label = Innermost(spans)
    programs = collections.defaultdict(lambda: {"seconds": 0.0, "runs": 0})
    scopes = collections.defaultdict(lambda: collections.defaultdict(float))
    idle = collections.defaultdict(float)
    n_dev = 0
    for plane in space.planes:
        if not tracing.DEVICE_PLANE.match(plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if tracing.OPS_LINE not in lines:
            continue
        n_dev += 1
        runs = []
        if MODULES_LINE in lines:
            for name, s, e, _ in events(plane, lines[MODULES_LINE]):
                fn = module_name(name)
                runs.append((s, e, fn))
                p = programs[fn]
                p["seconds"] += max(0.0, min(e, t1) - max(s, t0)) * 1e-9
                p["runs"] += t0 <= (s + e) / 2 < t1
        runs.sort()
        run_starts = [s for s, _, _ in runs]
        iv = []
        for name, s, e, tf_op in events(plane, lines[tracing.OPS_LINE]):
            cs, ce = max(s, t0), min(e, t1)
            if ce <= cs:
                continue
            iv.append((cs, ce))
            if tracing.CONTROL.match(tracing.OP_NAME.match(name).group(1)):
                continue
            i = bisect.bisect_right(run_starts, s) - 1
            if i >= 0 and e <= runs[i][1]:
                scopes[runs[i][2]][scope_of(tf_op)] += (ce - cs) * 1e-9
        for s, e in tracing.gaps(iv, t0, t1):
            idle[label((s + e) / 2)] += (e - s) * 1e-9
    if not n_dev:
        raise ValueError("the trace holds no device operations")
    inside = collections.defaultdict(lambda: {"count": 0, "seconds": 0.0})
    for n, s, e in spans:
        if n in SPANS and t0 <= s and e <= t1:
            inside[n]["count"] += 1
            inside[n]["seconds"] += (e - s) * 1e-9
    top_idle = sorted(((k, v / n_dev) for k, v in idle.items()),
                      key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (t1 - t0) * 1e-9,
        "programs": {fn: {"seconds": p["seconds"] / n_dev,
                          "runs": p["runs"] / n_dev}
                     for fn, p in programs.items()},
        "scopes": {fn: {k: v / n_dev for k, v in sc.items()}
                   for fn, sc in scopes.items()},
        "program_spans": dict(inside),
        "idle_gaps": [[k, v] for k, v in top_idle],
    }


def reduce_file(path, span_names: set[str]) -> dict:
    return reduce(load_file(path), span_names)


def reduce_dir(d, span_names: set[str]) -> dict:
    paths = sorted(pathlib.Path(d).rglob("*.xplane.pb"))
    if not paths:
        raise ValueError(f"no trace under {d}")
    return reduce_file(paths[-1], span_names)
