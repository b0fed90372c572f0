"""Quickstart: the paper's geodesic operators through the public API.

    PYTHONPATH=src python examples/quickstart.py

Two ways in: the *expression API* (compose a graph, compile once,
execute many times — composites fuse into one padded program) and the
classic operator sugar, which is thin wrappers over the same compiles.
"""
import numpy as np
import jax.numpy as jnp

from repro.api import E, asf_expr, compile, dome_expr, hmax_expr
from repro.core import operators as OPS
from repro.core.compile_cache import enable_compile_cache
from repro.data.images import blobs
from repro.kernels import ops

enable_compile_cache()

# a "Male"-like test image: smooth background + multi-scale blobs
img = blobs(256, 256, np.uint8)
f = jnp.asarray(img)

# --- expression API: compose -> compile -> execute ----------------------
x = E.input("f")
er64 = compile(x >> E.erode(64), f.shape, f.dtype)(f)     # 129×129 erosion
print("erode_64:   min", int(er64.min()), "max", int(er64.max()))

open16 = compile(E.opening(16, x), f.shape, f.dtype)(f)
print("opening_16: mean", float(open16.mean()))

# geodesic reconstruction with kernel-fused convergence detection
rec_expr = E.reconstruct(E.input("marker"), E.input("mask"), op="erode")
rec = compile(rec_expr, f.shape, f.dtype)(jnp.maximum(f, 100), f)
print("reconstruct: fixpoint reached, mean", float(rec.mean()))

# composite graphs fuse end-to-end: ASF_3 is ONE padded program
asf3 = compile(asf_expr(3), f.shape, f.dtype)
print("asf_3:      tv-smoothed       ->", float(asf3(f).std()),
      "| program:", asf3.stats())

hm = compile(hmax_expr(40), f.shape, f.dtype)
dm = compile(dome_expr(40), f.shape, f.dtype)
print("hmax_40:    maxima suppressed ->", int(hm(f).max()))
print("dome_40:    residue max       ->", int(dm(f).max()))

# --- classic sugar (same compiles underneath) ---------------------------
print("hfill:      holes filled      ->", int(OPS.hfill(f).min()))
print("raobj:      border objs gone  ->", int(OPS.raobj(f).max()))
d = OPS.qdt(f)
print("qdt:        max distance      ->", int(d.max()))
ps = OPS.pattern_spectrum(f, 8)
print("pattern spectrum (s=0..7):", np.asarray(ps, np.int64))
er = ops.erode(f, 16)   # kernels sugar routes through the same cache
print("kernels.ops.erode(16): mean   ->", float(er.mean()))
