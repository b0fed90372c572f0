"""Reduction of a profiler trace of the window to device numbers.

``read`` takes the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
two kinds of events, in nanoseconds on the profiler's one clock:

* device operations: the ``XLA Ops`` line of every ``/device:TPU:<n>``
  plane.  On a TPU each event is named by its HLO instruction's text
  (``%closed_call.5 = (u8[9216,1024]{...}, ...) custom-call(...),
  custom_call_target="tpu_custom_call", ...``) and carries no op name
  or name stack, so an operation is known by its opcode and, for a
  custom call, its target;
* host spans: the benchmark's own ``bench.*`` annotations, from any
  host plane.

``reduce`` turns them into the numbers the per-layer readers use:

* device busy time: the union of the operation intervals inside the
  ``bench.window`` span, averaged over the devices;
* launches and device time of the Pallas (Mosaic) kernels, the custom
  calls with target ``tpu_custom_call``, and the device time of every
  other operation that runs alone (``xla_s``);
* the operations that took the most time, and the longest idle gaps,
  each named by the host span that was open in its middle.

A ``while``, ``conditional`` or ``call`` appears in the line as one
event that spans the operations of its body; it counts towards busy
time, never towards an operation's own time.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

#: Operations whose event spans the events of their body.
CONTAINERS = frozenset({"while", "conditional", "call"})

#: Custom-call target of a Pallas kernel compiled by Mosaic.
PALLAS_TARGET = "tpu_custom_call"

TOP = 10

_LAYOUT = re.compile(r"\{[^{}]*\}")
_HEAD = re.compile(r"^%(?P<name>\S+) = (?P<shape>\([^()]*\)|\S+) "
                   r"(?P<opcode>[a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_KIND = re.compile(r"kind=(k\w+)")


def _device_plane(name: str) -> bool:
    return re.fullmatch(r"/device:TPU:\d+", name) is not None


def read(trace_dir: str) -> dict:
    """Events of the newest trace under ``trace_dir``."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(paths[-1], "rb") as f:
        return read_xspace(f.read())


def read_xspace(data: bytes) -> dict:
    """Events of one serialized ``XSpace`` (the ``.xplane.pb`` file)."""
    import jax

    space = jax.profiler.ProfileData.from_serialized_xspace(data)
    ops, spans = [], []
    for plane in space.planes:
        lines = list(plane.lines)
        if not _device_plane(plane.name):
            spans += [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for line in lines for e in line.events
                      if e.name.startswith("bench.")]
            continue
        for line in lines:
            if line.name == "XLA Ops":
                ops += [[plane.name, e.name, int(e.start_ns),
                         int(e.duration_ns)] for e in line.events]
    return {"ops": ops, "spans": spans}


def describe(text: str) -> tuple[str, str, str | None]:
    """``(short name, opcode, custom-call target)`` of an operation named
    by its HLO text; the short name keeps the instruction's name, shape,
    opcode and a custom call's target or a fusion's kind.  A name that
    is not HLO text is its own short name, with the opcode ``""``."""
    flat = _LAYOUT.sub("", text)
    m = _HEAD.match(flat)
    target = _TARGET.search(text)
    target = target.group(1) if target else None
    if m is None:
        return text[:120], "", target
    short = f"%{m['name']} = {m['shape']} {m['opcode']}"
    kind = _KIND.search(text) if m["opcode"] == "fusion" else None
    if target or kind:
        short += f" {target or kind.group(1)}"
    return short, m["opcode"], target


def _union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce(events: dict, window: str = "bench.window") -> dict:
    """Device numbers of the span named ``window`` (see module doc)."""
    wins = [(s, s + d) for n, s, d in events["spans"] if n == window]
    if not wins:
        raise ValueError(f"the trace holds no {window!r} span")
    w0, w1 = wins[-1]
    devices = sorted({op[0] for op in events["ops"]})
    names: dict[str, tuple] = {}
    busy_ns = pallas_ns = xla_ns = launches = 0
    per_op: dict[str, int] = {}
    first_busy = []
    for dev in devices:
        iv = []
        for plane, text, s, d in events["ops"]:
            if plane != dev:
                continue
            s, e = max(s, w0), min(s + d, w1)
            if e <= s:
                continue
            iv.append((s, e))
            if text not in names:
                names[text] = describe(text)
            short, opcode, target = names[text]
            if opcode in CONTAINERS:
                continue
            per_op[short] = per_op.get(short, 0) + (e - s)
            if target == PALLAS_TARGET:
                launches += 1
                pallas_ns += e - s
            else:
                xla_ns += e - s
        merged = _union(iv)
        busy_ns += sum(e - s for s, e in merged)
        if dev == devices[0]:
            first_busy = merged
    n_dev = max(1, len(devices))
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / n_dev / 1e9,
        "devices": len(devices),
        "pallas_launches": launches / n_dev,
        "pallas_s": pallas_ns / n_dev / 1e9,
        "xla_s": xla_ns / n_dev / 1e9,
        "device_ops": [[n, t / n_dev / 1e9] for n, t in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": _gaps(first_busy, w0, w1, events["spans"], window),
    }


def idle_pct(reduced: dict | None) -> float | None:
    """Share of the traced window in which no operation ran on the
    device, in percent; ``None`` without a trace of a device."""
    if (reduced is None or not reduced["devices"]
            or reduced["window_s"] <= 0):
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def _gaps(busy, w0, w1, spans, window) -> list:
    """The longest idle stretches of one device inside the window, each
    named by the innermost host span open at its middle."""
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    inner = sorted((s, s + d, n) for n, s, d in spans if n != window)
    starts = [s for s, _, _ in inner]
    out = []
    for g0, g1 in gaps[:TOP]:
        mid = (g0 + g1) // 2
        name = "no_span"
        best = None
        for s, e, n in inner[:bisect.bisect_right(starts, mid)]:
            if s <= mid < e and (best is None or e - s < best):
                name, best = n, e - s
        out.append([name, (g1 - g0) / 1e9])
    return out
