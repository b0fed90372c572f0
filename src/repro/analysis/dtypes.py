"""Dtype-safety checks (check class b): absorbing fills, the kernels'
work dtype and QDT overflow.

Three families of facts are proved per supported dtype (the paper's
char→double crossover set, §4):

* the serve bucketer's pad fill (``serve/bucketer.py:pad_fill``) must
  equal the lattice identity the kernels pin halos with
  (``kernels/common.py:ident_for``) and round-trip through the image
  dtype exactly — a fill one ULP off the lattice top is no longer
  absorbing for erosion and corrupts borders silently;
* every value of the image dtype round-trips exactly through the
  dtype the kernels compute in (``core/chain.py:work_dtype``) — a work
  dtype that rounds an image value changes min/max results silently;
  for uint8…int16 in float32 the fact is proved by exhaustion;
* the quasi-distance transform accumulates residuals
  ``f − ε₁(f)`` into ``kernels/common.py:qdt_acc_dtype``; the residual
  telescoping bound is the lattice range (one erosion can drop a pixel
  from top to bottom), so the accumulator must represent
  ``top − bottom``.  When the image dtype's own range cannot overflow
  the accumulator the fact is a proof (uint8…int16); when overflow
  needs pathological-but-representable inputs it is a WARN
  (int32 images in an int32 accumulator, float64 in float32).

Every check takes the *claimed* value as an argument with the
production default, so the mutation self-tests can seed a wrong fill, a
lossy work dtype or an undersized accumulator and assert detection.
"""
from __future__ import annotations

import numpy as np

from repro.analysis.findings import ERROR, WARN, Finding

#: Supported image dtypes, uint8 through float64 (ISSUE 6 scope).
SUPPORTED_DTYPES = ("uint8", "uint16", "int16", "int32",
                    "float32", "float64")

#: pad-fill name → the op whose lattice identity it must be.
FILL_OP = {"hi": "erode", "lo": "dilate"}


def _lattice(dtype):
    """(top, bottom) of the dtype's complete lattice as numpy scalars."""
    dtype = np.dtype(dtype)
    if np.issubdtype(dtype, np.floating):
        return np.array(np.inf, dtype), np.array(-np.inf, dtype)
    info = np.iinfo(dtype)
    return np.array(info.max, dtype), np.array(info.min, dtype)


def check_fill_value(dtype, which: str, value) -> list:
    """Is ``value`` the absorbing identity ``which`` for ``dtype``?"""
    out = []
    subject = f"pad_fill({np.dtype(dtype).name}, {which!r})"
    top, bot = _lattice(dtype)
    expect = top if which == "hi" else bot
    got = np.asarray(value)
    if got.dtype != np.dtype(dtype):
        # a float fill for an int image (or vice versa) silently casts
        # at pad time; require the exact dtype round-trip
        cast = got.astype(np.dtype(dtype))
        if not np.array_equal(cast.astype(got.dtype), got, equal_nan=True):
            out.append(Finding(
                "dtype", ERROR, subject,
                f"fill {got!r} is not representable in {np.dtype(dtype)}"))
            return out
        got = cast
    if not np.array_equal(got, expect, equal_nan=True):
        out.append(Finding(
            "dtype", ERROR, subject,
            f"fill is {got!r}, but the absorbing identity for "
            f"{FILL_OP[which]} is {expect!r} — pad values would "
            "participate in the min/max and corrupt borders"))
    return out


def check_bucketer_fills(dtypes=SUPPORTED_DTYPES) -> list:
    """Audit ``serve.bucketer.pad_fill`` against the kernel identities."""
    from repro.kernels.common import ident_for
    from repro.serve.bucketer import pad_fill

    out = []
    for dt in dtypes:
        for which, op in FILL_OP.items():
            out += check_fill_value(dt, which, pad_fill(dt, which))
            # the serve fill and the in-kernel pin must agree too
            kern = np.asarray(ident_for(op, dt))
            serve = np.asarray(pad_fill(dt, which))
            if not np.array_equal(kern, serve, equal_nan=True):
                out.append(Finding(
                    "dtype", ERROR, f"pad_fill({dt}, {which!r})",
                    f"serve fill {serve!r} != kernel halo identity "
                    f"{kern!r} (ident_for)"))
    return out


def check_work_dtype(image_dtype, work=None) -> list:
    """Does ``work`` (default: the kernels' ``work_dtype``) hold every
    value of ``image_dtype`` exactly, so that it round-trips and keeps
    its order?

    Integer images of at most 16 bits are enumerated, value by value (a
    proof by exhaustion); wider ones are bounded: an integer dtype must
    contain the image's range, a floating one must hold every integer of
    it (magnitude ≤ 2^(mantissa + 1)).  A floating image needs a
    floating work dtype at least as precise and as wide."""
    if work is None:
        from repro.core.chain import work_dtype
        work = work_dtype(image_dtype)
    img, wk = np.dtype(image_dtype), np.dtype(work)
    subject = f"work dtype ({img.name} image → {wk.name})"
    if img == wk:
        return []
    if np.issubdtype(img, np.floating):
        fi = np.finfo(img)
        exact = (np.issubdtype(wk, np.floating)
                 and np.finfo(wk).nmant >= fi.nmant
                 and np.finfo(wk).maxexp >= fi.maxexp
                 and np.finfo(wk).minexp <= fi.minexp)
    elif img.itemsize <= 2:
        info = np.iinfo(img)
        every = np.arange(int(info.min), int(info.max) + 1).astype(img)
        with np.errstate(all="ignore"):
            held = every.astype(wk)
        # the same value, not merely the same bits back: a wrap keeps
        # the round trip but breaks the order min/max rely on
        exact = (np.array_equal(held.astype(np.float64),
                                every.astype(np.float64))
                 and np.array_equal(held.astype(img), every))
    elif np.issubdtype(wk, np.floating):
        info = np.iinfo(img)
        exact = max(-int(info.min), int(info.max)) <= 2 ** (
            np.finfo(wk).nmant + 1)
    else:
        exact = (np.issubdtype(wk, np.integer)
                 and np.iinfo(wk).min <= np.iinfo(img).min
                 and np.iinfo(wk).max >= np.iinfo(img).max)
    if exact:
        return []
    return [Finding(
        "dtype", ERROR, subject,
        f"some {img.name} values do not survive the round trip through "
        f"{wk.name} — the kernels would compute min/max on rounded "
        "values and lose bit-exactness")]


def check_work_dtypes(dtypes=SUPPORTED_DTYPES) -> list:
    """Audit ``core.chain.work_dtype`` for every supported image dtype."""
    out = []
    for dt in dtypes:
        out += check_work_dtype(dt)
    return out


def check_qdt_accumulator(image_dtype, acc_dtype=None) -> list:
    """Can ``acc_dtype`` hold QDT residuals of ``image_dtype`` images?

    The residual is ``f − ε₁(f)`` with both operands cast to the
    accumulator first; its tight bound is ``top − bottom`` of the image
    lattice.
    """
    if acc_dtype is None:
        from repro.kernels.common import qdt_acc_dtype
        acc_dtype = qdt_acc_dtype(image_dtype)
    img, acc = np.dtype(image_dtype), np.dtype(acc_dtype)
    subject = f"qdt acc ({img.name} image → {acc.name} accumulator)"
    out = []

    if np.issubdtype(img, np.floating):
        if not np.issubdtype(acc, np.floating):
            out.append(Finding(
                "dtype", ERROR, subject,
                "floating image accumulated in an integer dtype — "
                "fractional residuals truncate"))
            return out
        if np.finfo(img).max > np.finfo(acc).max:
            out.append(Finding(
                "dtype", WARN, subject,
                f"residual bound 2·{np.finfo(img).max:.3g} exceeds "
                f"{acc.name} max {np.finfo(acc).max:.3g}: residuals of "
                "full-range images saturate to inf (distance planes "
                "stay ordered, values lose precision)"))
        return out

    if np.issubdtype(acc, np.floating):
        # integer residuals are exact in an integer accumulator; a
        # float accumulator breaks bit-exactness above 2^mantissa
        mant = np.finfo(acc).nmant
        if int(np.iinfo(img).max) - int(np.iinfo(img).min) > 2 ** mant:
            out.append(Finding(
                "dtype", ERROR, subject,
                f"integer residual bound exceeds the {acc.name} "
                f"mantissa (2^{mant}) — accumulation is no longer "
                "bit-exact"))
        return out

    bound = int(np.iinfo(img).max) - int(np.iinfo(img).min)
    acc_max = int(np.iinfo(acc).max)
    if bound > acc_max:
        # provable within the dtype's normal domain for narrow images,
        # domain-conditional for >= 32-bit images
        severity = ERROR if np.iinfo(img).bits < 32 else WARN
        out.append(Finding(
            "dtype", severity, subject,
            f"residual bound top−bottom = {bound} exceeds {acc.name} "
            f"max {acc_max} — a single erosion step can overflow the "
            "masked-store accumulator"
            + ("" if severity == ERROR else
               " (requires images spanning more than the accumulator "
               "range; unreachable for uint8/uint16 sources)")))
    return out


def check_distance_plane(max_chunks: int, fuse_k: int) -> list:
    """The d-plane stores ``base + k`` elementary-step indices in int32."""
    out = []
    max_d = int(max_chunks) * int(fuse_k)
    if max_d > np.iinfo(np.int32).max:
        out.append(Finding(
            "dtype", ERROR, "qdt distance plane",
            f"max distance index {max_d} (max_chunks={max_chunks} × "
            f"fuse_k={fuse_k}) overflows the int32 d-plane"))
    return out


def check_executable_dtypes(exe) -> list:
    """Dtype facts bound to one executable: QDT accumulation for its
    image dtype and d-plane headroom for its chunk budget."""
    out = []
    dt = np.dtype(exe.dtype)
    if dt.name not in SUPPORTED_DTYPES:
        out.append(Finding(
            "dtype", WARN, f"dtype {dt.name}",
            f"outside the audited set {SUPPORTED_DTYPES}"))
    if any(s.kind == "qdt" for s in exe.program.segments):
        out += check_qdt_accumulator(dt)
        if exe.plan is not None:
            out += check_distance_plane(exe._max_chunks_qdt, exe.plan.fuse_k)
    if (dt.kind != "f"
            and any(s.kind == "gdt" for s in exe.program.segments)):
        out.append(Finding(
            "dtype", ERROR, f"gdt on {dt.name}",
            "the generalised geodesic distance plane is a float lattice "
            "(+inf pad identity, fractional grey weights) — integer "
            "images must be cast to a float dtype before compilation"))
    return out
