"""Per-arch smoke tests: REDUCED same-family configs, one real forward +
train step + decode step on CPU, asserting shapes and finiteness."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config, ARCH_NAMES
from repro.models.lm import (init_model, forward, build_train_step,
                             build_serve_step, init_decode_cache)

B, S = 2, 16


def _batch(cfg, key):
    kt, kl, kf = jax.random.split(key, 3)
    batch = {
        "tokens": jax.random.randint(kt, (B, S), 0, cfg.vocab),
        "labels": jax.random.randint(kl, (B, S), 0, cfg.vocab),
    }
    if cfg.family == "encdec":
        batch["frames"] = 0.5 * jax.random.normal(kf, (B, S, cfg.d_model))
    if cfg.family == "vlm":
        batch["img"] = 0.5 * jax.random.normal(
            kf, (B, cfg.n_img_tokens, cfg.d_model))
    return batch


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_train_step_smoke(name):
    cfg = smoke_config(name)
    key = jax.random.PRNGKey(0)
    params = init_model(key, cfg)
    batch = _batch(cfg, jax.random.PRNGKey(1))
    logits, aux = jax.jit(lambda p, b: forward(p, cfg, b))(params, batch)
    assert logits.shape == (B, S, cfg.vocab)
    assert bool(jnp.isfinite(logits).all()), name
    loss, grads = jax.jit(build_train_step(cfg))(
        params, batch, jax.random.PRNGKey(2))
    assert np.isfinite(float(loss)), name
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))
    # at least one Σ leaf receives nonzero gradient
    import jax.tree_util as jtu
    s_norms = [float(jnp.linalg.norm(g))
               for path, g in jtu.tree_flatten_with_path(grads)[0]
               if str(getattr(path[-1], "key", "")) == "s" and g.ndim > 0]
    assert s_norms and max(s_norms) > 0, name


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_serve_step_smoke(name):
    cfg = smoke_config(name)
    key = jax.random.PRNGKey(0)
    params = init_model(key, cfg)
    cache = init_decode_cache(cfg, B, S)
    batch = {"token": jnp.zeros((B, 1), jnp.int32),
             "cache_len": jnp.asarray(3, jnp.int32)}
    if cfg.family == "vlm":
        batch["img"] = 0.5 * jax.random.normal(
            jax.random.PRNGKey(1), (B, cfg.n_img_tokens, cfg.d_model))
    if cfg.family == "encdec":
        batch["enc_out"] = 0.5 * jax.random.normal(
            jax.random.PRNGKey(1), (B, S, cfg.d_model))
    logits, new_cache = jax.jit(build_serve_step(cfg))(params, cache, batch)
    assert logits.shape == (B, cfg.vocab)
    assert bool(jnp.isfinite(logits).all()), name
    assert jax.tree.structure(new_cache) == jax.tree.structure(cache)


# whisper-base's serve step takes the encoder output from outside
@pytest.mark.parametrize("name", [n for n in ARCH_NAMES
                                  if n != "whisper-base"])
def test_decode_matches_prefill_logits(name):
    """Teacher-forced decode reproduces the prefill logits (same params,
    same tokens) — the serve path and the train path run one body."""
    cfg = smoke_config(name)
    key = jax.random.PRNGKey(0)
    params = init_model(key, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    extra = {k: v for k, v in _batch(cfg, jax.random.PRNGKey(2)).items()
             if k == "img"}
    logits_all, _ = forward(params, cfg, {"tokens": toks, **extra})
    cache = init_decode_cache(cfg, B, S)
    serve = jax.jit(build_serve_step(cfg))
    for i in range(4):
        batch = {"token": toks[:, i: i + 1],
                 "cache_len": jnp.asarray(i, jnp.int32), **extra}
        logits_i, cache = serve(params, cache, batch)
        np.testing.assert_allclose(np.asarray(logits_i),
                                   np.asarray(logits_all[:, i]),
                                   atol=2e-2, rtol=2e-2)


def test_chunked_attention_matches_full():
    import dataclasses
    cfg = smoke_config("olmo-1b")
    cfgc = dataclasses.replace(cfg, attn_chunk=4)
    params = init_model(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg, jax.random.PRNGKey(1))
    l1, _ = forward(params, cfg, batch)
    l2, _ = forward(params, cfgc, batch)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               atol=1e-3, rtol=1e-3)
