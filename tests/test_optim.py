"""Optimizers: AdamW semantics, frozen masking, ZO search, compression."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim.optimizers import (AdamWConfig, SGDConfig, init_opt_state,
                                    apply_updates, clip_by_global_norm)
from repro.optim.zo import ZOConfig, zo_minimize
from repro.optim.compression import (init_compression, compress_decompress,
                                     CompressionState)
from repro.optim.schedules import cosine_schedule, linear_warmup_cosine


def test_adamw_converges_quadratic():
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3)}
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0)
    state = init_opt_state(params)
    for _ in range(300):
        g = {"w": 2 * (params["w"] - target)}
        params, state, _ = apply_updates(params, g, state, cfg)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=1e-2)


def test_frozen_leaves_untouched():
    params = {"s": jnp.ones(4), "u": jnp.ones(4)}
    tr = {"s": True, "u": False}
    state = init_opt_state(params, tr)
    assert state.master["u"].shape == ()          # scalar placeholder
    g = {"s": jnp.ones(4), "u": jnp.ones(4)}
    p2, state, _ = apply_updates(params, g, state, AdamWConfig(),
                                 trainable=tr)
    assert float(jnp.abs(p2["u"] - 1.0).max()) == 0.0
    assert float(jnp.abs(p2["s"] - 1.0).max()) > 0.0


def test_bf16_params_fp32_master():
    params = {"s": jnp.ones(4, jnp.bfloat16)}
    state = init_opt_state(params)
    assert state.master["s"].dtype == jnp.float32
    g = {"s": jnp.full(4, 1e-3, jnp.bfloat16)}
    cfg = SGDConfig(lr=1e-4, momentum=0.0)
    p, state, _ = apply_updates(params, g, state, cfg)
    assert p["s"].dtype == jnp.bfloat16
    # master accumulates below bf16 resolution
    assert float(state.master["s"][0]) != 1.0


def test_clip_global_norm():
    g = {"a": jnp.full(4, 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    np.testing.assert_allclose(float(norm), 20.0)
    np.testing.assert_allclose(
        float(jnp.linalg.norm(clipped["a"])), 1.0, rtol=1e-5)


@pytest.mark.parametrize("method", ["zcd", "ztp", "zgd"])
def test_zo_minimizes_quadratic(method):
    target = jnp.asarray([0.5, -0.3, 0.8, 0.0])

    def loss(x):
        return jnp.sum((x - target) ** 2)

    cfg = ZOConfig(steps=400, inner=20, delta0=0.3, decay=1.1,
                   delta_min=1e-3, lr0=0.05)
    res = zo_minimize(loss, jnp.zeros(4), jax.random.PRNGKey(0), cfg,
                      method=method)
    assert float(res.f) < float(loss(jnp.zeros(4)))
    assert float(res.f) < 0.12, float(res.f)
    # best-recording: history is monotone non-increasing
    h = np.asarray(res.history)
    assert (np.diff(h) <= 1e-9).all()


def test_zo_vmappable():
    def loss(x):
        return jnp.sum(x ** 2)
    cfg = ZOConfig(steps=100, delta0=0.3)
    x0 = jnp.ones((5, 3))
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    res = jax.vmap(lambda x, k: zo_minimize(loss, x, k, cfg))(x0, keys)
    assert res.x.shape == (5, 3)
    assert (np.asarray(res.f) < 3.0).all()


def test_compression_error_feedback():
    """int8 EF: single-step error bounded by quant step; accumulated
    updates converge to the true sum (EF property)."""
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal(256), jnp.float32)
    err = jnp.zeros(256)
    total_dq = jnp.zeros(256)
    for _ in range(50):
        dq, err = compress_decompress(g, err)
        total_dq += dq
    np.testing.assert_allclose(np.asarray(total_dq / 50), np.asarray(g),
                               atol=float(jnp.abs(g).max()) / 127 + 1e-3)


def test_psum_compressed_single_device():
    """shard_map psum path on a 1-device mesh (semantics check)."""
    from repro.optim.compression import psum_compressed
    from jax.sharding import AxisType, PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    g = {"w": jnp.asarray([1.0, -2.0, 3.0])}
    st = init_compression(g)

    def f(g, e):
        out, st2 = psum_compressed(g, CompressionState(error=e), "data")
        return out, st2.error

    fm = jax.shard_map(f, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()))
    out, err = fm(g, st.error)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(g["w"]),
                               atol=3 / 127 + 1e-4)


def test_schedules():
    assert float(cosine_schedule(0, 100)) == 1.0
    assert float(cosine_schedule(100, 100)) < 1e-6
    assert float(linear_warmup_cosine(0, 10, 100)) == 0.0
    assert 0.9 < float(linear_warmup_cosine(10, 10, 100)) <= 1.0
