"""Distributed morphology (``repro.core.distributed``): the sharded
chain and reconstruction equal the single-device references.

The multi-device run is a subprocess with 8 fake host devices, because
XLA_FLAGS must be set before jax initializes and the main test process
keeps its single-device view."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_subprocess(code: str) -> str:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_distributed_chain_and_reconstruct_equivalence():
    out = run_subprocess("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core import distributed as D, morphology as M

        mesh = jax.make_mesh((4, 2), ("r", "c"))
        rng = np.random.default_rng(3)
        f = jnp.asarray(rng.integers(0, 256, (96, 96), np.uint8))
        put = lambda x: jax.device_put(x, NamedSharding(mesh, P("r", "c")))

        fn = D.distributed_chain(mesh, "r", "c", n=9, op="erode",
                                 backend="xla", fuse_k=4)
        np.testing.assert_array_equal(
            np.asarray(fn(put(f))), np.asarray(M.erode(f, 9)))

        m = jnp.asarray(rng.integers(0, 256, (96, 96), np.uint8))
        marker = jnp.maximum(f, m)
        rec = D.distributed_reconstruct(mesh, "r", "c", op="erode",
                                        backend="xla", fuse_k=4)
        np.testing.assert_array_equal(
            np.asarray(rec(put(marker), put(m))),
            np.asarray(M.erode_reconstruct(marker, m)))
        print("EQUIV_OK")
    """)
    assert "EQUIV_OK" in out
