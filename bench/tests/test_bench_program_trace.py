"""The reduction by the program's own names (``bench/program_trace.py``):
on a hand-built trace laid out as ``read_xspace`` returns one, on the
recorded v5e trace of a program that had no such names, and on a
profiler trace of a ``Service`` taken on the CPU; and the readers of the
new metrics, which read nothing from a program without the names."""
import gzip
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import load, program_trace as P, spec
from bench import trace as T
from repro.serve.service import hlo_head

MS = 1_000_000  # ns
DEV = "/device:TPU:0"

NAMED = ('%geodesic_tile.3 = (u8[64,128]{1,0:T(8,128)(4,1)}, s32[2]{0}) '
         'custom-call(s32[2]{0} %a, u8[64,128]{1,0} %f), '
         'custom_call_target="tpu_custom_call", frontend_attributes='
         '{kernel_metadata={\n"kernel":"geodesic_tile"\n}}, '
         'metadata={op_name="jit(f)/geodesic_tile/pallas_call"}')
UNNAMED = ('%closed_call.5 = (u8[2304,1024]{1,0:T(8,128)(4,1)S(1)}, '
           's32[6]{0:T(128)}) custom-call(s32[6]{0:T(128)S(1)} %b), '
           'custom_call_target="tpu_custom_call", '
           'frontend_attributes={kernel_metadata={}}')
GATHER = ('%fusion.12 = u8[9437184]{0:T(1024)} fusion(u8[256,192,2048] '
          '%broadcast_select_fusion.1, s32[9437184] %reshape.338), '
          'kind=kCustom, calls=%fused_computation.3.clone')
LOOP = ('%while = (s32[]{:T(128)}) while((s32[]{:T(128)}) %tuple.29), '
        'condition=%wide.region_1.4, body=%wide.region_0.3')
SCOPES = {"%fusion.12 = u8[9437184] fusion kCustom": "compact_gather"}


def events():
    return {
        "ops": [
            [DEV, NAMED, 12 * MS, 8 * MS],           # 12..20
            [DEV, LOOP, 30 * MS, 10 * MS],           # 30..40, busy only
            [DEV, GATHER, 30 * MS, 5 * MS],          # 30..35
            [DEV, UNNAMED, 35 * MS, 5 * MS],         # 35..40
        ],
        "spans": [
            ["bench.window", 10 * MS, 40 * MS],      # 10..50
            ["bench.pump", 10 * MS, 18 * MS],        # 10..28
            ["bench.wait", 41 * MS, 3 * MS],         # 41..44
        ],
        "program_spans": [
            ["serve.drain", 15 * MS, 11 * MS, {"batch": "4"}],   # 15..26
            ["serve.wait", 18 * MS, 7 * MS, {"batch": "4"}],     # 18..25
            ["serve.submit", 44 * MS + MS // 2, MS // 2,
             {"request": "9"}],                                  # 44.5..45
            ["serve.submit", 60 * MS, MS, {"request": "10"}],    # after
        ],
    }


def test_split_args():
    assert P.split_args("serve.launch#batch=3,n=2#") == (
        "serve.launch", {"batch": "3", "n": "2"})
    assert P.split_args("serve.stage") == ("serve.stage", {})


def test_idle_time_is_split_by_innermost_span():
    """Idle 10..12 (pump), 20..30 (wait, drain, pump, none), 40..50
    (none, wait, none, submit, none); the parts make up the idle
    share."""
    ev = events()
    r = P.reduce_program(ev)
    assert r["idle_s"] == {"serve": pytest.approx(0.0065),
                           "bench": pytest.approx(0.007),
                           "none": pytest.approx(0.0085)}
    idle = T.idle_pct(T.reduce(ev))
    assert idle == pytest.approx(55.0)
    assert abs(sum(r["idle_pct"].values()) - idle) < 1e-9
    assert r["idle_pct"]["serve"] == pytest.approx(16.25)


def test_kernels_by_name_and_gathers_by_scope():
    r = P.reduce_program(events(), SCOPES)
    assert r["kernels"] == {"geodesic_tile": [1, pytest.approx(0.008)]}
    assert r["unnamed_launches"] == 1
    assert r["gather_s"] == pytest.approx(0.005)
    assert P.reduce_program(events())["gather_s"] is None
    # spans that overlap the window, by name
    assert r["spans"] == {"serve.drain": [1, pytest.approx(0.011)],
                          "serve.wait": [1, pytest.approx(0.007)],
                          "serve.submit": [1, pytest.approx(0.0005)]}
    assert r["program_spans"] == 3


def test_kernel_name_as_quoted_in_a_trace():
    assert P.kernel_of(NAMED) == "geodesic_tile"
    assert P.kernel_of('kernel_metadata="{\\"kernel\\":\\"qdt_row\\"}"') \
        == "qdt_row"
    assert P.kernel_of(UNNAMED) is None


RECORDED = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "testdata", "hmax_short.xplane.pb.gz")


def test_recorded_trace_of_a_program_without_names():
    """The recorded v5e trace predates the program's names: no
    ``serve.*`` span, and none of its 19 launches names its kernel."""
    with open(RECORDED, "rb") as f:
        ev = P.read_xspace(gzip.decompress(f.read()))
    assert ev["program_spans"] == []
    r = P.reduce_program(ev, {})
    assert r["kernels"] == {} and r["unnamed_launches"] == 19
    assert r["gather_s"] == 0
    assert r["idle_s"]["serve"] == 0
    assert abs(sum(r["idle_pct"].values())
               - T.idle_pct(T.reduce(ev))) < 1e-9


def test_program_heads_match_trace_short_names():
    """``Service.op_scopes`` keys and ``bench.trace.describe`` name an
    instruction alike, so that a trace event finds its scope."""
    def f(x, idx):
        with jax.named_scope("compact_gather"):
            g = jnp.take_along_axis(x, idx, axis=1) + 1
        return g, jax.lax.while_loop(lambda c: c < 3, lambda c: c + 1, 0)

    text = jax.jit(f).lower(jnp.zeros((8, 128), jnp.uint8),
                            jnp.zeros((8, 4), jnp.int32)).compile().as_text()
    lines = [ln.strip().removeprefix("ROOT ") for ln in text.splitlines()
             if " = " in ln and ln.strip().startswith(("%", "ROOT %"))]
    assert len(lines) > 3
    for ln in lines:
        assert hlo_head(ln) == T.describe(ln)[0], ln


def _contains(outer, inner):
    return outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]


def test_service_trace_nests_spans(tmp_path):
    """A profiler trace of a Service on the CPU holds its spans nested
    as documented, each batch's spans sharing its id."""
    from repro.serve import Service

    rng = np.random.default_rng(3)
    svc = Service(backend="xla", max_batch=2, max_delay_ms=1e9,
                  pad_quantum=16)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        tickets = [svc.submit("hmax", rng.integers(0, 255, (16, 16))
                              .astype(np.uint8), params={"h": 10})
                   for _ in range(3)]
        svc.flush()
    jax.profiler.stop_trace()
    assert all(t.outcome == "ok" for t in tickets)
    ev = P.read(str(tmp_path))
    spans = ev["program_spans"]
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    assert {"serve.submit", "serve.launch", "serve.compile", "serve.stage",
            "serve.dispatch", "serve.drain", "serve.wait",
            "serve.demux"} <= set(by)
    assert sorted(s[3]["request"] for s in by["serve.submit"]) == [
        "0", "1", "2"]
    assert len(by["serve.launch"]) == 2
    for outer, inner in (("serve.launch", ("serve.stage", "serve.dispatch")),
                         ("serve.drain", ("serve.wait", "serve.demux"))):
        for o in by[outer]:
            for name in inner:
                assert any(_contains(o, i) and i[3]["batch"] == o[3]["batch"]
                           for i in by[name]), (outer, name)
    assert all(any(_contains(o, c) for o in by["serve.launch"])
               for c in by["serve.compile"])
    r = P.reduce_program(ev)
    assert r["program_spans"] == len(spans)


READERS = ("queue_wait_p50_ms", "run_wait_p50_ms", "host_blocked_pct",
           "idle_in_service_pct.stream", "idle_in_service_pct.tiles",
           "gather_ms_per_mpx", "gathers_per_tile")


class _OldTicket:
    """A ticket of a program without stamps or a service handle."""

    def __init__(self, t):
        self.t_enqueue, self.t_done = t, t + 0.05


def _run(tickets, totals, trace):
    sent = [types.SimpleNamespace(ticket=t, pixels=4 * 2**20, ok=True)
            for t in tickets]
    return types.SimpleNamespace(sent=sent, answered=sent, trace=trace,
                                 stats={"totals": totals})


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_from_a_program_without_names(name):
    """The new metrics read what this program adds; on a program without
    it (old tickets, old counters, no ``serve.*`` span) each reader
    returns nothing instead of raising."""
    old_totals = {"requests": 2, "busy_chunks": 14, "batches": 1}
    # a traced run whose trace holds no serve.* span
    reduced = {"devices": 1, "program_spans": 0,
               "idle_pct": {"serve": 0.0, "bench": 0.0, "none": 0.0}}
    run = _run([_OldTicket(0.0), _OldTicket(1.0)], old_totals,
               {"devices": 1, "program": reduced})
    assert spec.reader(name)(run) is None


def test_stamp_and_counter_readers():
    def ticket(t0):
        return types.SimpleNamespace(t_enqueue=t0, t_launch=t0 + 0.004,
                                     t_dispatch=t0 + 0.006,
                                     t_ready=t0 + 0.026, t_done=t0 + 0.03)

    tickets = [ticket(0.0), ticket(1.0), ticket(2.0)]
    totals = {"requests": 3, "compact_chunks": 12, "mask_gathers": 3,
              "host_blocked_s": 0.5, "span_s": 2.0}
    run = _run(tickets, totals,
               {"devices": 1, "program": P.reduce_program(events())})
    assert spec.reader("queue_wait_p50_ms")(run) == pytest.approx(
        load.percentile([4.0] * 3, 50))
    assert spec.reader("run_wait_p50_ms")(run) == pytest.approx(20.0)
    # 7 ms of serve.wait in the 40 ms window
    assert spec.reader("host_blocked_pct")(run) == pytest.approx(17.5)
    assert spec.reader("idle_in_service_pct.stream")(run) == \
        pytest.approx(16.25)
    assert spec.reader("gathers_per_tile")(run) == pytest.approx(5.0)
    # the stamps time the device's path: a run whose trace saw no
    # device reads nothing
    off = _run(tickets, totals, {"devices": 0})
    assert spec.reader("run_wait_p50_ms")(off) is None
    assert spec.reader("host_blocked_pct")(off) is None
