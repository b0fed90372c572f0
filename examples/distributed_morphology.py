"""Distributed geodesic reconstruction over a device mesh with halo
exchange — the paper's pipeline scaled out (DESIGN.md §6).

Run with fake devices to see the sharded path on CPU:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python examples/distributed_morphology.py
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import distributed as D
from repro.core import morphology as M
from repro.core.compile_cache import enable_compile_cache
from repro.data.images import blobs

enable_compile_cache()
n = len(jax.devices())
rows = max(1, n // 2)
cols = n // rows
mesh = jax.make_mesh((rows, cols), ("r", "c"))
print(f"mesh: {rows}x{cols} over {n} devices")

img = blobs(512, 512, np.uint8)
f = jnp.asarray(img)
m = jnp.asarray(blobs(512, 512, np.uint8, seed=9))
marker = jnp.maximum(f, m)
put = lambda x: jax.device_put(x, NamedSharding(mesh, P("r", "c")))  # noqa: E731

# 64-step chain: halo exchanged once per 16 fused steps (4 exchanges)
chain = D.distributed_chain(mesh, "r", "c", n=64, op="erode", fuse_k=16)
out = chain(put(f))
ref = M.erode(f, 64)
print("chain sharded == single-device:",
      bool(jnp.array_equal(out, ref)))

rec = D.distributed_reconstruct(mesh, "r", "c", op="erode", fuse_k=16)
out = rec(put(marker), put(m))
ref = M.erode_reconstruct(marker, m)
print("reconstruct sharded == single-device:",
      bool(jnp.array_equal(out, ref)))
print("per-device shards:", out.sharding.shard_shape(out.shape))
