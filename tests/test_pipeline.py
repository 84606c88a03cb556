"""GPipe pipeline parallelism over the pod axis: exactness vs the
standard forward, gradient flow (subprocess: 8 forced devices)."""

import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import jax, jax.numpy as jnp, dataclasses
from repro.configs import smoke_config
from repro.models.lm import init_model, forward, cross_entropy
from repro.launch.pipeline import build_pp_loss

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)
cfg = dataclasses.replace(smoke_config("olmo-1b"), n_layers=4, remat=True)
params = init_model(jax.random.PRNGKey(0), cfg)
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                      cfg.vocab),
         "labels": jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0,
                                      cfg.vocab)}
logits, _ = forward(params, cfg, batch)
ref = float(cross_entropy(logits, batch["labels"]))
pp = build_pp_loss(cfg, n_stages=2, n_micro=2)
with mesh:
    got = float(jax.jit(lambda p, b: pp(p, b, mesh))(params, batch))
    assert abs(ref - got) < 1e-5, (ref, got)
    g = jax.jit(jax.grad(lambda p, b: pp(p, b, mesh)))(params, batch)
    gref = jax.grad(lambda p: cross_entropy(
        forward(p, cfg, {"tokens": batch["tokens"]})[0],
        batch["labels"]))(params)
    a = g["pos0"]["attn"]["wq"]["s"]
    b = gref["pos0"]["attn"]["wq"]["s"]
    assert float(jnp.abs(a - b).max()) < 1e-5
print("PP_OK")
"""


@pytest.mark.slow
def test_pp_matches_standard_forward_8dev():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert "PP_OK" in r.stdout, r.stderr[-2000:]
