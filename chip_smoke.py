#!/usr/bin/env python3
"""Smoke test: the served morphology path, natively on one TPU chip.

Drives ``repro.serve.Service`` (the ``compile`` -> ``Executable`` ->
``Service`` path a user calls) at the paper's 1024x1024 frame size and
checks every result bit for bit against a plain reference:

1. fixed long chain: ``erode`` with s=1536 on four frames, uint8 and
   float32, against ``repro.core.morphology.erode``, and a chain of 100
   whose answer, unlike s=1536's, is not the frame minimum;
2. reconstruction: ``hmax`` (h=40) on four uint8 frames, on the batch
   path and on the continuous slot-refill path, against
   ``repro.core.morphology.dilate_reconstruct``;
3. segmentation: ``seg_scribble`` and ``gdt`` on a pinned float32 image,
   against the XLA-backend executable of the same expression, plus one
   256x256 ``gdt`` against the NumPy oracle ``repro.gdt.gdt_reference``.

Every phase also checks that its programs lower to the Mosaic kernel
(``tpu_custom_call``) and that no ticket came back degraded.  The
seconds it prints are one run's smoke timings, not a benchmark.

    python3 chip_smoke.py               # the phases above, one chip
    python3 chip_smoke.py --four-chips  # only the sharded 2x2-mesh path

``--four-chips`` runs ``core.distributed``'s chain and reconstruction
on 4096x4096 uint8 over a 2x2 mesh and compares them with the
one-device result.  The last line of standard output is one JSON
object, ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": N}}``.  The script exits non-zero, without that line, when
JAX finds no TPU or any check fails.  JAX's persistent compilation
cache is kept where ``repro.core.compile_cache`` says.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SIZE = 1024
FRAMES = 4


class SmokeError(RuntimeError):
    """A smoke check failed."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeError(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# inputs
# ---------------------------------------------------------------------------


def ramp(size: int) -> np.ndarray:
    """uint8 ramp rising one grey level every ``size // 256`` columns:
    ``hmax`` must carry its wavefront ~40 levels' worth of columns, so
    it converges several chunks after a blob frame does."""
    x = np.arange(size) // max(1, size // 256)
    return np.broadcast_to(np.minimum(x, 255).astype(np.uint8),
                           (size, size)).copy()


def frames(size: int, dtype, n: int = FRAMES) -> list:
    from repro.data.images import blobs

    return [blobs(size, size, dtype, seed=i) for i in range(n)]


def scribbles(image: np.ndarray) -> np.ndarray:
    """Scribble plane (0 unmarked, 1 foreground, 2 background): a stroke
    through the brightest pixel's row, one down the darkest's column."""
    h, w = image.shape
    fy, fx = np.unravel_index(int(image.argmax()), image.shape)
    by, bx = np.unravel_index(int(image.argmin()), image.shape)
    s = np.zeros(image.shape, np.float32)
    s[fy, max(0, fx - w // 16): fx + w // 16] = 1.0
    s[max(0, by - h // 16): by + h // 16, bx] = 2.0
    return s


# ---------------------------------------------------------------------------
# checks shared by the phases
# ---------------------------------------------------------------------------


def mosaic_kernels(svc) -> bool:
    """True iff every compiled bucket program of ``svc`` (and each slot
    session's round) lowers to a Mosaic kernel."""
    import jax

    texts = []
    for entry in svc.cache.entries():
        exe = entry.exe
        shape = (exe.n_images, exe.height, exe.width)
        args = [jax.ShapeDtypeStruct(shape, exe.dtype)
                for _ in exe.program.run_input_slots]
        texts.append(jax.jit(entry.primary()).lower(*args).as_text())
        if svc.continuous and exe.refillable:
            session = exe.slot_session(svc.refill_quantum)
            state = jax.eval_shape(session.init)
            texts.append(session.round.lower(state).as_text())
    return bool(texts) and all("tpu_custom_call" in t for t in texts)


def results(tickets) -> list:
    """Every ticket's result; none may be degraded."""
    outs = [np.asarray(t.result()) for t in tickets]
    check(not any(t.degraded for t in tickets), "a ticket was degraded")
    return outs


def clean(svc) -> dict:
    """The service's stats, checked for failed or retried batches."""
    stats = svc.stats()
    counters = stats["counters"]
    for name in ("batch_failures", "retried", "quarantine_reruns"):
        check(counters.get(name, 0) == 0, f"service counted {name}")
    return stats


def timed_serve(svc, warm, requests):
    """Warm the buckets (compile + one sentinel run), then submit the
    requests and collect them.  Returns (outputs, warm_s, wall_s)."""
    t0 = time.perf_counter()
    svc.warmup(warm)
    t1 = time.perf_counter()
    tickets = [svc.submit(op, *imgs, params=params)
               for op, imgs, params in requests]
    outs = results(tickets)
    return outs, t1 - t0, time.perf_counter() - t1


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_erode(size: int = SIZE, dtype: str = "uint8",
                s: int = 1536) -> dict:
    """Fixed long chain: ``erode`` s on four frames in one batch.

    A chain as long as s=1536 reaches past a 1024 px frame, so its
    answer is each frame's minimum everywhere and the comparison proves
    little.  The phase therefore also runs a chain shorter than the
    frame (100 at 1024 px), which crosses band and ``fuse_k`` edges and
    whose reference must not be constant."""
    import jax.numpy as jnp

    from repro.core import morphology as M
    from repro.serve import Service

    imgs = frames(size, dtype)
    svc = Service(backend="pallas", max_batch=FRAMES)
    chains = [{"s": s}, {"s": min(100, size // 4)}]
    outs, warm_s, wall_s = timed_serve(
        svc, [dict(op="erode", shape=(size, size), dtype=dtype,
                   params=params) for params in chains],
        [("erode", (im,), params) for params in chains for im in imgs])
    for i, params in enumerate(chains):
        ref = np.asarray(M.erode(jnp.asarray(np.stack(imgs)), params["s"]))
        if i:
            check(all(r.min() < r.max() for r in ref),
                  f"erode s={params['s']} {dtype}: reference is constant")
        got = outs[i * len(imgs):(i + 1) * len(imgs)]
        check(all(np.array_equal(o, r) for o, r in zip(got, ref)),
              f"erode s={params['s']} {dtype}: differs from "
              "core.morphology.erode")
    clean(svc)
    return dict(phase=f"erode-{dtype}", warm_s=warm_s, wall_s=wall_s,
                chunks=sum(e.plan.n_chunks for e in svc.cache.entries()),
                mosaic=mosaic_kernels(svc))


def phase_hmax(size: int = SIZE, continuous: bool = False,
               h: int = 40) -> dict:
    """Reconstruction: ``hmax`` h on four uint8 frames.  The continuous
    path holds two slots, so frames queue and refill finished slots
    while a slower frame keeps iterating."""
    import jax.numpy as jnp

    from repro.core import morphology as M
    from repro.core.operators import sat_sub
    from repro.serve import Service

    imgs = [ramp(size)] + frames(size, "uint8", FRAMES - 1)
    svc = Service(backend="pallas", continuous=continuous,
                  max_batch=2 if continuous else FRAMES, refill_quantum=1)
    params = {"h": h}
    outs, warm_s, wall_s = timed_serve(
        svc, [dict(op="hmax", shape=(size, size), dtype="uint8",
                   params=params)],
        [("hmax", (im,), params) for im in imgs])
    f = jnp.asarray(np.stack(imgs))
    ref = np.asarray(M.dilate_reconstruct(sat_sub(f, h), f))
    label = "continuous" if continuous else "batch"
    check(all(np.array_equal(o, r) for o, r in zip(outs, ref)),
          f"hmax {label}: differs from core.morphology.dilate_reconstruct")
    stats = clean(svc)
    refills = stats["counters"].get("refills", 0)
    if continuous:
        check(stats["totals"]["rounds"] > 0 and refills > 0,
              "hmax continuous: the slot engine did not refill a slot")
    return dict(phase=f"hmax-{label}", warm_s=warm_s, wall_s=wall_s,
                chunks=stats["totals"]["busy_chunks"], refills=refills,
                mosaic=mosaic_kernels(svc))


def phase_segment(size: int = SIZE, oracle_size: int = 256) -> dict:
    """Segmentation on a pinned image: ``seg_scribble`` and ``gdt``
    against the XLA-backend executable, and a small ``gdt`` against the
    NumPy oracle."""
    from repro import api
    from repro.gdt import gdt_expr, gdt_reference, seg_scribble_expr
    from repro.serve import Service

    (image,) = frames(size, "float32", 1)
    scrib = scribbles(image)
    seeds = (scrib == 1.0).astype(np.float32)
    (small,) = frames(oracle_size, "float32", 1)
    small_seeds = (scribbles(small) == 1.0).astype(np.float32)

    svc = Service(backend="pallas", max_batch=1)
    svc.pin("image", image)
    shape = (size, size)
    outs, warm_s, wall_s = timed_serve(
        svc, [dict(op="seg_scribble", shape=shape, dtype="float32"),
              dict(op="gdt", shape=shape, dtype="float32"),
              dict(op="gdt", shape=(oracle_size,) * 2, dtype="float32")],
        [("seg_scribble", ("image", scrib), None),
         ("gdt", ("image", seeds), None),
         ("gdt", (small, small_seeds), None)])
    seg, dist, small_dist = outs

    E = api.E
    xla = {
        "seg_scribble": api.compile(seg_scribble_expr(), shape, np.float32,
                                    "xla")(image, scrib),
        "gdt": api.compile(gdt_expr(E.input("image"), E.input("seeds")),
                           shape, np.float32, "xla")(image, seeds),
    }
    check(np.array_equal(seg, np.asarray(xla["seg_scribble"])),
          "seg_scribble: differs from the XLA-backend executable")
    check(np.array_equal(dist, np.asarray(xla["gdt"])),
          "gdt: differs from the XLA-backend executable")
    check(np.array_equal(small_dist, gdt_reference(small, small_seeds)),
          f"gdt {oracle_size}px: differs from the NumPy oracle")
    stats = clean(svc)
    return dict(phase="segment", warm_s=warm_s, wall_s=wall_s,
                chunks=stats["totals"]["busy_chunks"],
                mosaic=mosaic_kernels(svc))


def phase_four_chips(size: int = 4096, n: int = 64) -> dict:
    """``core.distributed`` chain and reconstruction on a 2x2 mesh with
    the policy-default backend, against one device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import distributed as D
    from repro.core import morphology as M
    from repro.core.chain import plan_chain
    from repro.data.images import blobs

    check(len(jax.devices()) >= 4, "--four-chips needs four devices")
    mesh = jax.make_mesh((2, 2), ("r", "c"))
    sharded = NamedSharding(mesh, P("r", "c"))
    base = min(size, SIZE)

    def frame(seed):  # blobs upsampled: cheap to make on the host
        up = size // base
        return jnp.asarray(blobs(base, base, np.uint8, seed=seed)
                           .repeat(up, axis=0).repeat(up, axis=1))

    f, m = frame(5), frame(6)
    marker = jnp.minimum(f, m)
    chain = D.distributed_chain(mesh, "r", "c", n=n, op="erode")
    rec = D.distributed_reconstruct(mesh, "r", "c", op="dilate")
    args_c = (jax.device_put(f, sharded),)
    args_r = (jax.device_put(marker, sharded), jax.device_put(m, sharded))

    t0 = time.perf_counter()
    lowered = [chain.lower(*args_c), rec.lower(*args_r)]
    chain_x, rec_x = (lo.compile() for lo in lowered)
    t1 = time.perf_counter()
    out_c = chain_x(*args_c).block_until_ready()
    out_r = rec_x(*args_r).block_until_ready()
    t2 = time.perf_counter()

    one = jax.devices()[0]
    ref_c = M.erode(jax.device_put(f, one), n)
    ref_r = M.dilate_reconstruct(jax.device_put(marker, one),
                                 jax.device_put(m, one))
    for name, out, ref in (("chain", out_c, ref_c),
                           ("reconstruct", out_r, ref_r)):
        check(len(out.sharding.device_set) == 4,
              f"four-chips {name}: output not sharded over 4 devices")
        check(np.array_equal(np.asarray(out), np.asarray(ref)),
              f"four-chips {name}: differs from the one-device result")
    return dict(phase="four-chips", warm_s=t1 - t0, wall_s=t2 - t1,
                chunks=-(-n // plan_chain(size // 2, size // 2, np.uint8,
                                          n).fuse_k),
                mosaic=all("tpu_custom_call" in lo.as_text()
                           for lo in lowered))


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded 2x2-mesh path")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{dev[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro.core.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2

    cache_dir = enable_compile_cache()
    cache = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    log(f"device_kind: {dev[0].device_kind}  devices: {len(dev)}  "
        f"jax: {jax.__version__}  compile cache: {cache_dir}")

    if args.four_chips:
        phases = [phase_four_chips]
    else:
        phases = [lambda: phase_erode(dtype="uint8"),
                  lambda: phase_erode(dtype="float32"),
                  lambda: phase_hmax(continuous=False),
                  lambda: phase_hmax(continuous=True),
                  phase_segment]
    for phase in phases:
        rep = phase()
        check(rep["mosaic"], f"{rep['phase']}: no Mosaic kernel lowered")
        log(f"smoke timing (not a benchmark) {rep['phase']}: "
            f"compile+warm {rep['warm_s']:.3f} s, wall {rep['wall_s']:.3f} s, "
            f"chunks {rep['chunks']}"
            + (f", refills {rep['refills']}" if "refills" in rep else ""))
    log(f"compile cache: {cache['hits']} hits, {cache['misses']} misses")
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
