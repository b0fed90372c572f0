"""The comparison that decides ``correct``.

Every request due in the window has to be answered, without error and
without the degraded (partially converged) flag, by a minute past the
window's close.  A sample of the answers, drawn from the seed, is
compared pixel for pixel with the plain reference (``bench.reference``
through the configuration's ``reference``), computed after the window
for every pool item.  The system promises bit-exact results, so both
limits are 0.
"""
from __future__ import annotations

import numpy as np

#: Answers a run compares: some hundreds of megapixels at the most.
SAMPLE = 96

#: name -> (limit, kind): "max" passes at or under the limit, "min" at
#: or over it.
LIMITS = {
    "failed_requests": (0, "max"),
    "mismatched_px": (0, "max"),
    "answers_compared": (1, "min"),
}


class Sample:
    """A uniform sample of ``size`` answers over the order in which they
    arrive (reservoir sampling with the seed's generator).  The values of
    answers that leave the sample are dropped at once, so that the
    window does not hold every answer on the device."""

    def __init__(self, rng, size: int = SAMPLE):
        self.rng = rng
        self.size = size
        self.kept: list = []
        self.seen = 0

    def offer(self, sent) -> None:
        self.seen += 1
        if len(self.kept) < self.size:
            self.kept.append(sent)
            return
        j = int(self.rng.integers(self.seen))
        if j < self.size:
            self.kept[j].ticket.value, self.kept[j] = None, sent
        else:
            sent.ticket.value = None


def mismatches(answers, items, expected: np.ndarray) -> int:
    """Pixels of ``answers`` that differ from ``expected[item]``; a
    wrong shape or dtype counts every pixel."""
    bad = 0
    for out, item in zip(answers, items):
        ref = expected[item]
        out = np.asarray(out)
        if out.shape != ref.shape or out.dtype != ref.dtype:
            bad += ref.size
        else:
            bad += int(np.count_nonzero(out != ref))
    return bad


def verdict(values: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"|"min"}})`` for the numbers
    compared."""
    out, ok = {}, True
    for name, (limit, kind) in LIMITS.items():
        v = values[name]
        if kind == "max":
            ok &= v <= limit
            out[name] = {"value": v, "limit": limit}
        else:
            ok &= v >= limit
            out[name] = {"value": v, "min": limit}
    return bool(ok), out
