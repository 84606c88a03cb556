"""MoE EP paths: a2a (shard_map all_to_all) ≡ pjit path, multi-device.

Runs in a subprocess with 8 forced host devices so the main test
process keeps its single-device view (conftest contract)."""

import subprocess
import sys
import os

import pytest

SCRIPT = r"""
import jax, jax.numpy as jnp
from repro.models.ffn import MoECfg, init_moe, moe
from repro.models.layers import PTCLinearCfg
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
ptc = PTCLinearCfg(k=8, mode="fused", base_dtype=jnp.float32)
kw = dict(d_model=32, d_ff=64, n_experts=8, top_k=2, capacity_factor=8.0)
cfg_p = MoECfg(dispatch="pjit", **kw)
cfg_a = MoECfg(dispatch="a2a", **kw)
p = init_moe(jax.random.PRNGKey(0), cfg_p, ptc)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32))
with mesh:
    yp, _ = jax.jit(lambda p, x: moe(p, cfg_p, ptc, x))(p, x)
    ya, _ = jax.jit(lambda p, x: moe(p, cfg_a, ptc, x))(p, x)
    assert float(jnp.abs(yp - ya).max()) < 1e-5, "forward mismatch"
    gx_a = jax.jit(jax.grad(lambda p, x: moe(p, cfg_a, ptc, x)[0].sum(),
                            argnums=1))(p, x)
    gx_p = jax.jit(jax.grad(lambda p, x: moe(p, cfg_p, ptc, x)[0].sum(),
                            argnums=1))(p, x)
    assert float(jnp.abs(gx_a - gx_p).max()) < 1e-4, "dx mismatch"
    gs_a = jax.jit(jax.grad(lambda p, x: moe(p, cfg_a, ptc, x)[0].sum()))(p, x)
    gs_p = jax.jit(jax.grad(lambda p, x: moe(p, cfg_p, ptc, x)[0].sum()))(p, x)
    err = max(float(jnp.abs(a - b).max())
              for a, b in zip(jax.tree.leaves(gs_a), jax.tree.leaves(gs_p)))
    assert err < 1e-4, f"param grad mismatch {err}"
print("A2A_OK")
"""


@pytest.mark.slow
def test_a2a_matches_pjit_8dev():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "A2A_OK" in r.stdout, r.stderr[-2000:]


def test_a2a_falls_back_single_device():
    """On 1 device (no mesh) the a2a config transparently uses the pjit
    path — smoke configs keep working everywhere."""
    import jax
    import jax.numpy as jnp
    from repro.models.ffn import MoECfg, init_moe, moe
    from repro.models.layers import PTCLinearCfg
    ptc = PTCLinearCfg(k=8, mode="fused", base_dtype=jnp.float32)
    cfg = MoECfg(d_model=32, d_ff=64, n_experts=4, top_k=2, dispatch="a2a")
    p = init_moe(jax.random.PRNGKey(0), cfg, ptc)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
    y, aux = jax.jit(lambda p, x: moe(p, cfg, ptc, x))(p, x)
    assert y.shape == x.shape and bool(jnp.isfinite(y).all())
