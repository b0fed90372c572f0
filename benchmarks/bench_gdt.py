"""Generalised geodesic distance suite: wavefront requeue scheduling
vs the raster-scan sweep schedule vs the L1 quasi-distance baseline.

One image, one sparse seed set, three engines for the same fixpoint
(all bit-exact with ``repro.gdt.gdt_reference``):

* ``wavefront`` — the chunked activity-grid scheduler (the repo's
  requeue machinery, ``ChainPlan.schedule="wavefront"``); the derived
  column carries its chunk-weighted utilization (busy/capacity);
* ``raster`` — FastGeodis-style down/up/left/right sweeps iterated to
  fixpoint (``schedule="raster"``);
* ``xla`` — the pure-jnp Jacobi oracle;
* ``qdt_l1`` — the existing binary L1 quasi-distance kernel on the
  thresholded image, the λ=0 bridge (grey weights off, integer
  lattice): what gdt generalises.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import timeit
from repro import api
from repro.core.chain import plan_chain
from repro.data.images import blobs
from repro.kernels import ops as K


def _case(size: int):
    img = (blobs(size, size, np.uint8).astype(np.float32) / 255.0) * 3.0
    rng = np.random.default_rng(7)
    seeds = (rng.random((size, size)) < 4.0 / size).astype(np.float32)
    seeds[size // 2, size // 2] = 1.0
    return jnp.asarray(img), jnp.asarray(seeds)


def run(quick: bool = True):
    size = 128 if quick else 512
    lamb, nu = 1.0, float(2 * size)
    img, seeds = _case(size)
    expr = api.E.gdt(api.E.input("image"), api.E.input("seeds"),
                     lamb=lamb, nu=nu)
    rows = []

    wave = api.compile(expr, img.shape, img.dtype, "pallas")
    t = timeit(lambda: wave(img, seeds), repeats=2)
    _, conv, busy, cap, _, _ = wave.run_batch_stats(img[None], seeds[None])
    util = float(busy) / float(cap) if int(cap) else 1.0
    rows.append({
        "name": f"gdt/wavefront/{size}px",
        "us_per_call": t * 1e6,
        "derived": f"lamb={lamb} converged={bool(conv.all())} "
                   f"chunk_util={util:.2f}",
    })

    raster_plan = plan_chain(size, size, np.float32, None,
                             n_images_resident=3, n_images=1,
                             convergent=True, schedule="raster")
    raster = api.compile(expr, img.shape, img.dtype, "pallas",
                         plan=raster_plan)
    tr = timeit(lambda: raster(img, seeds), repeats=2)
    rows.append({
        "name": f"gdt/raster/{size}px",
        "us_per_call": tr * 1e6,
        "derived": f"lamb={lamb} vs_wavefront={t / tr:.2f}x",
    })

    xla = api.compile(expr, img.shape, img.dtype, "xla")
    tx = timeit(lambda: xla(img, seeds), repeats=2)
    rows.append({
        "name": f"gdt/xla/{size}px",
        "us_per_call": tx * 1e6,
        "derived": f"lamb={lamb} vs_wavefront={t / tx:.2f}x",
    })

    # λ=0 bridge baseline: binary L1 quasi-distance on the thresholded
    # image (the transform gdt reduces to when grey weights are off)
    binary = jnp.asarray(
        (np.asarray(img) > np.asarray(img).mean()).astype(np.uint8) * 255)
    tq = timeit(lambda: K.qdt_planes(binary, backend="pallas"), repeats=2)
    rows.append({
        "name": f"gdt/qdt_l1/{size}px",
        "us_per_call": tq * 1e6,
        "derived": f"binary_baseline vs_wavefront={t / tq:.2f}x",
    })
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit
    emit(run())
