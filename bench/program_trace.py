"""Reduction of a profiler trace of the window by the program's own
names, beside ``bench.trace``, which names what it finds by HLO text and
the harness's ``bench.*`` spans alone.

The program names three things a TPU trace keeps:

* host spans ``serve.*`` (``repro.serve.metrics.span``): ``submit``,
  ``launch`` (holding ``compile``, ``stage``, ``dispatch``), ``drain``
  (holding ``wait`` and ``demux``), each with TraceMe arguments such as
  ``batch=<id>``;
* each Pallas kernel's name, in the ``kernel_metadata`` frontend
  attribute of its ``tpu_custom_call`` (``{"kernel":"geodesic_tile"}``,
  printed across lines in the event's text);
* the scheduler's ``jax.named_scope`` names, which a device event does
  not carry: ``repro.serve.Service.op_scopes()`` maps each compiled
  instruction's head (``bench.trace.describe``'s short name) to its
  scope, and the events are joined to it by that head.

``read_xspace`` keeps what ``bench.trace.read_xspace`` keeps and adds
``program_spans``.  ``reduce_program`` splits the window's idle device
time by the innermost host span open at each idle instant, sums the
device time of the ``compact_gather`` operations, and counts launches
and time per kernel name.  A program without these names (no
``serve.*`` span, no ``op_scopes``) reads as nothing, never as zero.
"""
from __future__ import annotations

import glob
import heapq
import os
import re

from bench import trace as T

#: The scheduler scope whose device time ``gather_ms_per_mpx`` reads.
GATHER_SCOPE = "compact_gather"

#: Where the innermost span at an idle instant can come from.
PARTS = ("serve", "bench", "none")

#: A kernel's name in ``kernel_metadata``: printed bare across lines
#: (``={\n"kernel":"gdt_tile"\n}``) in a v5e trace, or as an escaped
#: string.
_KERNEL = re.compile(
    r'kernel_metadata="?\{\s*\\?"kernel\\?"\s*:\s*\\?"(\w+)')


def read(trace_dir: str) -> dict:
    """Events of the newest trace under ``trace_dir``."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(paths[-1], "rb") as f:
        return read_xspace(f.read())


def split_args(name: str) -> tuple[str, dict]:
    """``("serve.launch", {"batch": "3"})`` from a TraceMe name that
    still carries its ``#batch=3#`` argument suffix."""
    head, sep, rest = name.partition("#")
    if not sep:
        return name, {}
    args = {}
    for pair in rest.rstrip("#").split(","):
        k, eq, v = pair.partition("=")
        if eq:
            args[k] = v
    return head, args


def read_xspace(data: bytes) -> dict:
    """``bench.trace.read_xspace``'s events plus ``program_spans``:
    ``[name, start_ns, duration_ns, args]`` for every host event whose
    name starts ``serve.``."""
    import jax

    events = T.read_xspace(data)
    space = jax.profiler.ProfileData.from_serialized_xspace(data)
    spans = []
    for plane in space.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if not e.name.startswith("serve."):
                    continue
                name, args = split_args(e.name)
                args.update((k, str(v)) for k, v in e.stats)
                spans.append([name, int(e.start_ns), int(e.duration_ns),
                              args])
    events["program_spans"] = spans
    return events


def kernel_of(text: str) -> str | None:
    """The kernel name in an operation's ``kernel_metadata``."""
    m = _KERNEL.search(text)
    return m.group(1) if m else None


def _part(name: str) -> str:
    return "serve" if name.startswith("serve.") else "bench"


def _idle_parts(busy, w0: int, w1: int, spans) -> dict:
    """Nanoseconds of ``[w0, w1)`` outside ``busy`` (merged intervals),
    by the part of the innermost span open there: the open span that
    started last."""
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = sorted(spans)
    out = dict.fromkeys(PARTS, 0)
    open_: list = []        # (-start, end, name): innermost on top
    i = 0
    for g0, g1 in gaps:
        t = g0
        while t < g1:
            while i < len(spans) and spans[i][0] <= t:
                s, e, n = spans[i]
                heapq.heappush(open_, (-s, e, n))
                i += 1
            while open_ and open_[0][1] <= t:
                heapq.heappop(open_)
            nxt = g1
            if i < len(spans):
                nxt = min(nxt, spans[i][0])
            if open_:
                nxt = min(nxt, open_[0][1])
            out[_part(open_[0][2]) if open_ else "none"] += nxt - t
            t = nxt
    return out


def reduce_program(events: dict, scopes: dict | None = None,
                   window: str = "bench.window") -> dict:
    """The program's numbers of the span named ``window``:

    * ``idle_s``: idle device seconds in the window (averaged over the
      devices, as ``bench.trace.reduce`` averages busy time) by where
      the innermost host span open at that instant comes from: a
      ``serve.*`` span, a ``bench.*`` span or none (``window`` itself
      left out); ``idle_pct`` the same as shares of the window, which
      sum to ``bench.trace.idle_pct``;
    * ``gather_s``: device seconds of the operations whose short name
      ``scopes`` maps to ``compact_gather`` (``None`` without scopes);
    * ``kernels``: ``{name: [launches, seconds]}`` of the Pallas
      launches, by ``kernel_metadata``; ``unnamed_launches`` those that
      name no kernel;
    * ``spans``: ``{name: [count, seconds]}`` of the ``serve.*`` spans
      that overlap the window, their seconds clipped to it, and
      ``program_spans`` their total count.
    """
    wins = [(s, s + d) for n, s, d in events["spans"] if n == window]
    if not wins:
        raise ValueError(f"the trace holds no {window!r} span")
    w0, w1 = wins[-1]
    program = events.get("program_spans", [])
    hosts = [(s, s + d, n) for n, s, d in events["spans"] if n != window]
    hosts += [(s, s + d, n) for n, s, d, _ in program]
    devices = sorted({op[0] for op in events["ops"]})
    n_dev = max(1, len(devices))
    idle = dict.fromkeys(PARTS, 0)
    gather_ns = unnamed = 0
    kernels: dict[str, list] = {}
    names: dict[str, tuple] = {}
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
                names[text] = (*T.describe(text), kernel_of(text))
            short, opcode, target, kernel = names[text]
            if opcode in T.CONTAINERS:
                continue
            if target == T.PALLAS_TARGET:
                if kernel is None:
                    unnamed += 1
                else:
                    k = kernels.setdefault(kernel, [0, 0])
                    k[0] += 1
                    k[1] += e - s
            elif scopes is not None and scopes.get(short) == GATHER_SCOPE:
                gather_ns += e - s
        for part, ns in _idle_parts(T._union(iv), w0, w1, hosts).items():
            idle[part] += ns
    window_s = (w1 - w0) / 1e9
    idle_s = {p: ns / n_dev / 1e9 for p, ns in idle.items()}
    spans: dict[str, list] = {}
    for n, s, d, _ in program:
        s, e = max(s, w0), min(s + d, w1)
        if e > s:
            c = spans.setdefault(n, [0, 0.0])
            c[0] += 1
            c[1] += (e - s) / 1e9
    return {
        "window_s": window_s,
        "devices": len(devices),
        "idle_s": idle_s,
        "idle_pct": {p: 100.0 * v / window_s if window_s > 0 else 0.0
                     for p, v in idle_s.items()},
        "gather_s": None if scopes is None else gather_ns / n_dev / 1e9,
        "kernels": {k: [c / n_dev, t / n_dev / 1e9]
                    for k, (c, t) in sorted(kernels.items())},
        "unnamed_launches": unnamed / n_dev,
        "spans": spans,
        "program_spans": sum(c for c, _ in spans.values()),
    }


def on_device(run) -> bool:
    """Whether the run was traced and its trace saw a device.  The
    readers of the service's stamps and wait time read only such runs:
    off the chip the kernels run in an interpreter, synchronously, and
    those times say nothing of the served path."""
    return run.trace is not None and run.trace["devices"] > 0


def service_of(run):
    """The service that answered the run's requests (a ``Run`` holds
    its tickets, each of which knows its service), or ``None``."""
    for s in run.sent:
        svc = getattr(s.ticket, "_service", None)
        if svc is not None:
            return svc
    return None


def reduced(run, with_scopes: bool = False) -> dict | None:
    """``reduce_program`` of the run's trace (``None`` untraced), kept
    in ``run.trace`` under ``program`` so that the trace is read once
    for all the metrics that read it; with ``with_scopes``, joined to
    the service's ``op_scopes()`` (under ``program_scoped``), or
    ``None`` where the service has none."""
    if run.trace is None:
        return None
    key = "program_scoped" if with_scopes else "program"
    if key not in run.trace:
        from bench.run import TRACE_DIR

        scopes = None
        if with_scopes:
            op_scopes = getattr(service_of(run), "op_scopes", None)
            if op_scopes is None:
                run.trace[key] = None
                return None
            scopes = op_scopes()
        if "program_events" not in run.trace:
            run.trace["program_events"] = read(TRACE_DIR)
        run.trace[key] = reduce_program(run.trace["program_events"], scopes)
    return run.trace[key]
