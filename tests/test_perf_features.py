"""Correctness of the §Perf levers: a2a MoE dispatch, SP collectives,
int8 weight/KV quantization, serving sharding rules.

Multi-device equivalence tests run in a subprocess (the main pytest
process has already initialized jax with 1 CPU device; the probes need
--xla_force_host_platform_device_count=8).
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_subprocess(body: str):
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, {src!r})
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
    """).format(src=os.path.abspath(SRC)) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=500)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"


@pytest.mark.slow
def test_a2a_moe_matches_scatter_multidevice():
    _run_subprocess("""
        from repro.configs import get_arch, reduced
        from repro.nn import moe as moe_mod
        from repro.nn.dims import compute_dims
        from repro.nn.params import build_params
        from repro.parallel.sharding import use_mesh

        cfg0 = reduced(get_arch("llama4-scout-17b-a16e"))
        cfg = dataclasses.replace(cfg0, moe=dataclasses.replace(
            cfg0.moe, num_experts=4, capacity_factor=8.0, ep_impl="a2a"))
        cfg_s = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, ep_impl="scatter"))
        dims = compute_dims(cfg, tp=4)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        params = build_params(moe_mod.moe_spec(cfg, dims), jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                              jnp.float32)
        with use_mesh(mesh):
            y_a = jax.jit(lambda p, x: moe_mod.moe_ffn(p, x, cfg, dims))(params, x)
            y_s = jax.jit(lambda p, x: moe_mod.moe_ffn(p, x, cfg_s, dims))(params, x)
        d = np.abs(np.asarray(y_a, np.float32) - np.asarray(y_s, np.float32)).max()
        assert d < 2e-5, d
    """)


@pytest.mark.slow
def test_meshed_forward_matches_unmeshed_multidevice():
    """The explicit SP gather/reduce-scatter path is numerically the same
    model (bf16 tolerance) as the single-device path."""
    _run_subprocess("""
        from repro.configs import get_arch, reduced
        from repro.nn import model as model_lib
        from repro.nn.dims import compute_dims
        from repro.parallel.sharding import use_mesh

        for arch in ("tinyllama-1.1b", "llama4-scout-17b-a16e", "zamba2-1.2b"):
            cfg = reduced(get_arch(arch))
            dims = compute_dims(cfg, tp=4)
            params = model_lib.init_params(cfg, dims, jax.random.PRNGKey(0))
            toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                      cfg.vocab_size)
            ref = model_lib.forward(params, toks, cfg, dims, mode="train",
                                    remat=False)
            mesh = jax.make_mesh((2, 4), ("data", "model"),
                                 axis_types=(jax.sharding.AxisType.Auto,) * 2)
            with use_mesh(mesh):
                got = jax.jit(lambda p, t: model_lib.forward(
                    p, t, cfg, dims, mode="train", remat=False))(params, toks)
            d = np.abs(np.asarray(ref, np.float32)
                       - np.asarray(got, np.float32)).max()
            assert d < 0.15, (arch, d)
    """)


@pytest.mark.parametrize("arch_id", ["tinyllama-1.1b", "zamba2-1.2b"])
def test_kv8_prefill_decode_consistency(arch_id):
    """int8 KV cache: decode against a quantized prefill cache matches the
    full-precision forward within PTQ tolerance."""
    from repro.configs import get_arch, reduced
    from repro.nn import model as model_lib
    from repro.nn.dims import compute_dims
    cfg0 = reduced(get_arch(arch_id))
    cfg = dataclasses.replace(cfg0, kv_quant=True)
    dims = compute_dims(cfg, tp=1)
    params = model_lib.init_params(cfg, dims, jax.random.PRNGKey(0))
    b, s = 2, 33
    toks = jax.random.randint(jax.random.PRNGKey(2), (b, s), 0, cfg.vocab_size)
    full = model_lib.forward(params, toks, cfg0, dims, mode="train",
                             remat=False)
    _, cache = model_lib.forward(params, toks[:, :-1], cfg, dims,
                                 mode="prefill", s_max=s)
    # quantized cache layout
    leaves = jax.tree.leaves(cache)
    assert any(a.dtype == jnp.int8 for a in leaves)
    dec, new_cache = model_lib.decode(params, toks[:, -1:], cache,
                                      jnp.int32(s - 1), cfg, dims)
    a = np.asarray(full[:, -1], np.float32)
    c = np.asarray(dec[:, 0], np.float32)
    rel = np.abs(a - c).max() / (np.abs(a).max() + 1e-9)
    assert rel < 0.08, rel
    assert jax.tree.structure(new_cache) == jax.tree.structure(cache)


def test_lm_quant_roundtrip_and_axes():
    from repro.core import lm_quant
    from repro.configs import get_arch, reduced
    from repro.nn import model as model_lib
    from repro.nn.dims import compute_dims
    cfg = reduced(get_arch("qwen1.5-0.5b"), width=256)
    dims = compute_dims(cfg, tp=1)
    params = model_lib.init_params(cfg, dims, jax.random.PRNGKey(0))
    q = lm_quant.quantize_params(params)
    back = lm_quant.dequantize_params(q)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    # big weights roundtrip within one quantization step (checked in f32 —
    # the bf16 output dtype adds its own representation rounding)
    back32 = lm_quant.dequantize_params(q, dtype=jnp.float32)
    emb = params["embed"]["embedding"].astype(jnp.float32)
    emb_q = q["embed"]["embedding"]
    assert emb_q["q"].dtype == jnp.int8
    err = jnp.abs(emb - back32["embed"]["embedding"]).max()
    assert float(err) <= float(emb_q["s"]) * 0.51 + 1e-6
    # axes tree mirrors the quantized structure (axes tuples are leaves)
    from repro.parallel.sharding import is_logical_leaf
    p_axes = model_lib.param_axes(cfg, dims)
    q_axes = lm_quant.quantized_axes(model_lib.abstract_model_params(cfg, dims),
                                     p_axes)
    norm_axes = jax.tree.map(lambda _: 0, q_axes, is_leaf=is_logical_leaf)
    norm_q = jax.tree.map(lambda _: 0, q)
    assert jax.tree.structure(norm_axes) == jax.tree.structure(norm_q)


def test_serving_rules_drop_fsdp():
    from repro.parallel.sharding import serving_rules, spec_for

    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")
    mesh = FakeMesh()
    rules = serving_rules(mesh)
    assert rules["fsdp"] == ()
    spec = spec_for((4096, 4096), ("fsdp", "ffn"), mesh, rules)
    assert spec[0] is None and spec[1] == "model"
