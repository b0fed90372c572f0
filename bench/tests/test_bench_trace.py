"""The reduction from trace events to device numbers: on a hand-built
trace laid out as ``bench.trace.read`` returns a TPU profile (each
device operation named by its HLO instruction's text), and on a trace
recorded on a TPU v5e (``bench/testdata/hmax_short.xplane.pb.gz``: a
2 s ``--trace 1`` window of ``tissue.hmax``, four 2048x2048 tiles)."""
import gzip
import os

import pytest

from bench import trace as T

MS = 1_000_000  # ns

KERNEL = ('%closed_call.5 = (u8[2304,1024]{1,0:T(8,128)(4,1)S(1)}, '
          's32[6]{0:T(128)}) custom-call(s32[6]{0:T(128)S(1)} %broadcast.4, '
          'u8[2304,1024]{1,0:T(8,128)(4,1)S(1)} %copy.17), '
          'custom_call_target="tpu_custom_call", '
          'frontend_attributes={kernel_metadata={}}')
GATHER = ('%fusion.12 = u8[9437184]{0:T(1024)} fusion(u8[256,192,2048] '
          '%broadcast_select_fusion.1, s32[9437184] %reshape.338), '
          'kind=kCustom, calls=%fused_computation.3.clone')
LOOP = ('%while = (s32[]{:T(128)}, u8[2304,1024]{1,0:T(8,128)(4,1)S(1)}) '
        'while((s32[]{:T(128)}, u8[2304,1024]{1,0:T(8,128)(4,1)S(1)}) '
        '%tuple.29), condition=%wide.region_1.4, body=%wide.region_0.3')


def events():
    dev = "/device:TPU:0"
    return {
        "ops": [
            # (plane, HLO text, start, duration)
            [dev, "%copy.1 = u8[8]{0} copy(u8[8]{0} %p)", 0, 5 * MS],  # out
            [dev, GATHER, 9 * MS, 3 * MS],                   # 10..12
            [dev, LOOP, 12 * MS, 5 * MS],                    # 12..17
            [dev, KERNEL, 12 * MS, 4 * MS],                  # inside it
            [dev, KERNEL, 15 * MS, 2 * MS],                  # inside it
            [dev, KERNEL, 30 * MS, 10 * MS],
            [dev, "after", 45 * MS, 20 * MS],                # 45..50 kept
        ],
        "spans": [
            ["bench.window", 10 * MS, 40 * MS],              # 10..50
            ["bench.pump", 10 * MS, 25 * MS],                # 10..35
            ["bench.wait", 17 * MS, 3 * MS],                 # 17..20
            ["bench.submit", 40 * MS, 5 * MS],               # 40..45
        ],
    }


def test_busy_is_the_union_inside_the_window():
    r = T.reduce(events())
    assert r["window_s"] == pytest.approx(0.040)
    # 10..17 (merged), 30..40, 45..50
    assert r["busy_s"] == pytest.approx(0.022)
    assert r["devices"] == 1


def test_families_gathers_and_top_ops():
    """Pallas kernels are the ``tpu_custom_call`` custom calls; a loop's
    event counts towards busy time only, never as an operation."""
    r = T.reduce(events())
    assert r["pallas_launches"] == 3
    assert r["pallas_s"] == pytest.approx(0.016)
    assert r["xla_s"] == pytest.approx(0.002 + 0.005)
    short = "%closed_call.5 = (u8[2304,1024], s32[6]) custom-call " \
            "tpu_custom_call"
    assert r["device_ops"][0] == [short, pytest.approx(0.016)]
    assert [n for n, _ in r["device_ops"]] == [
        short, "after", "%fusion.12 = u8[9437184] fusion kCustom"]


def test_describe_reads_hlo_text():
    assert T.describe(LOOP)[1] == "while"
    assert T.describe(KERNEL)[1:] == ("custom-call", "tpu_custom_call")
    assert T.describe(GATHER) == (
        "%fusion.12 = u8[9437184] fusion kCustom", "fusion", None)
    assert T.describe("fusion.1") == ("fusion.1", "", None)


def test_idle_gaps_are_named_by_innermost_span():
    r = T.reduce(events())
    gaps = r["idle_gaps"]
    # 17..30 (13 ms, middle 23.5: pump), 40..45 (5 ms: submit)
    assert gaps[0] == ["bench.pump", pytest.approx(0.013)]
    assert gaps[1] == ["bench.submit", pytest.approx(0.005)]
    ev = events()
    ev["spans"].append(["bench.wait", 20 * MS, 8 * MS])   # 20..28
    assert T.reduce(ev)["idle_gaps"][0][0] == "bench.wait"


def test_no_window_span_is_an_error():
    ev = events()
    ev["spans"] = ev["spans"][1:]
    with pytest.raises(ValueError):
        T.reduce(ev)


RECORDED = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "testdata", "hmax_short.xplane.pb.gz")


def test_recorded_chip_trace_reduces():
    with open(RECORDED, "rb") as f:
        ev = T.read_xspace(gzip.decompress(f.read()))
    assert {n for n, _, _ in ev["spans"]} == {
        "bench.window", "bench.pump", "bench.submit", "bench.wait"}
    r = T.reduce(ev)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(2.241478571)
    assert r["busy_s"] == pytest.approx(2.186954891)
    # two batches of two tiles: the tile and compact kernels' launches
    assert r["pallas_launches"] == 19
    assert r["pallas_s"] == pytest.approx(0.064745958)
    # the compaction's patch gathers take nearly all the device time
    assert r["xla_s"] == pytest.approx(2.122188873)
    top, secs = r["device_ops"][0]
    assert top == "%fusion.10 = u8[9437184] fusion kCustom"
    assert secs == pytest.approx(0.922307706)
    assert r["idle_gaps"][0] == ["bench.pump", pytest.approx(0.019189978)]
