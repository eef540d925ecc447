"""GPipe pipeline parallelism == sequential layer stack (subprocess: needs
8 virtual devices for a (data=2, stage=4) mesh)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.mark.slow
def test_pipeline_forward_matches_sequential():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, {src!r})
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.parallel.pipeline_parallel import (bubble_fraction,
                                                      pipeline_forward)

        L, D, n_micro, mb, S = 8, 16, 6, 2, 4
        key = jax.random.PRNGKey(0)
        k1, k2, k3 = jax.random.split(key, 3)
        params = {{
            "w": jax.random.normal(k1, (L, D, D)) * (D ** -0.5),
            "b": jax.random.normal(k2, (L, D)) * 0.1,
        }}
        x = jax.random.normal(k3, (n_micro, mb, S, D))

        def block_fn(lp, h):
            return jnp.tanh(h @ lp["w"] + lp["b"])

        # sequential reference
        def seq(h):
            for i in range(L):
                h = block_fn(jax.tree.map(lambda a: a[i], params), h)
            return h
        ref = jax.vmap(seq)(x)

        mesh = jax.make_mesh((2, 4), ("data", "stage"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        got = jax.jit(lambda p, x: pipeline_forward(
            p, x, block_fn, mesh, extra_specs=P("data", None, None)))(params, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
        assert abs(bubble_fraction(4, 6) - 3/9) < 1e-9
        print("pipeline == sequential OK")
    """).format(src=SRC)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=500)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
