"""The readers of ``staged_bytes_per_px.*``: the service's host↔device
byte counters over the pixels answered, in a run whose trace saw the
device; nothing from a program without the counters, or off the chip."""
import types

import pytest

from bench import spec

NAMES = ("staged_bytes_per_px.stream", "staged_bytes_per_px.tiles")
ON_CHIP = {"devices": 1}


def _run(totals, trace=ON_CHIP):
    return types.SimpleNamespace(stats={"totals": totals}, trace=trace)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("totals,trace,want", [
    # two 2048² u8 tiles, each put on the device once: 1 B/px
    ({"requests": 2, "pixels": 2 * 2048**2, "h2d_bytes": 2 * 2048**2,
      "d2h_bytes": 0}, ON_CHIP, 1.0),
    # the round trip each input took before staging moved to the device
    ({"requests": 2, "pixels": 2 * 2048**2, "h2d_bytes": 6 * 2048**2,
      "d2h_bytes": 4 * 2048**2}, ON_CHIP, 5.0),
    # a program without the counters
    ({"requests": 2, "busy_chunks": 14, "batches": 1}, ON_CHIP, None),
    # nothing answered
    ({"requests": 0, "pixels": 0, "h2d_bytes": 0, "d2h_bytes": 0},
     ON_CHIP, None),
    # off the chip, and untraced
    ({"requests": 1, "pixels": 4, "h2d_bytes": 4, "d2h_bytes": 0},
     {"devices": 0}, None),
    ({"requests": 1, "pixels": 4, "h2d_bytes": 4, "d2h_bytes": 0},
     None, None),
])
def test_reader(name, totals, trace, want):
    got = spec.reader(name)(_run(totals, trace))
    assert got == (None if want is None else pytest.approx(want))
