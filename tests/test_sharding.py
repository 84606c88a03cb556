"""Sharding rules: role classification, divisibility guard, spec shapes.

Uses AbstractMesh (no devices needed) so these run on the 1-CPU test
runner; the real 512-device lowering is exercised by launch/dryrun.py
and test_train_integration's subprocess test."""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import smoke_config, get_config
from repro.models.lm import init_model
from repro.launch.sharding import _leaf_spec, _path_names
import jax.tree_util as jtu


def _specs(cfg, model_size=16):
    pshapes = jax.eval_shape(lambda k: init_model(k, cfg),
                             jax.random.PRNGKey(0))
    out = {}
    for path, leaf in jtu.tree_flatten_with_path(pshapes)[0]:
        key = "/".join(_path_names(path))
        out[key] = (_leaf_spec(path, leaf, model_size), leaf.shape)
    return out


def test_out_projection_shards_p_axis():
    specs = _specs(get_config("qwen3-4b"))
    spec, shape = specs["pos0/attn/wq/u"]
    # (L, P, Q, k, k): P axis sharded
    assert spec[1] == "model" and spec[2] is None


def test_in_projection_shards_q_axis():
    specs = _specs(get_config("qwen3-4b"))
    spec, shape = specs["pos0/attn/wo/u"]
    assert spec[1] is None and spec[2] == "model"
    spec, _ = specs["pos0/mlp/down/s"]
    assert spec[2] == "model"


def test_gqa_small_kv_replicated():
    """qwen3-4b kv=8 heads × hd=128 = 1024 → P=8 blocks < 16 ⇒ the
    divisibility guard replicates wk/wv."""
    specs = _specs(get_config("qwen3-4b"))
    spec, shape = specs["pos0/attn/wk/u"]
    assert shape[1] == 8                      # P blocks
    assert all(s is None for s in spec)


def test_whisper_attention_replicated():
    """whisper-base attention dims (512 = 8 k-blocks) < TP ⇒ replicated;
    only the 2048-wide MLP (32 k=64-blocks) is eligible for TP."""
    specs = _specs(get_config("whisper-base"))
    for key, (spec, shape) in specs.items():
        if "/attn/" in key or "/cross/" in key or key.startswith("embed"):
            assert all(s != "model" for s in spec), (key, spec)


def test_moe_experts_shard_e_axis():
    specs = _specs(get_config("qwen3-moe-30b-a3b"))
    spec, shape = specs["pos0/moe/experts/gate/u"]
    # (L, E, P, Q, k, k): E axis sharded
    assert shape[1] == 128
    assert spec[1] == "model"
    rspec, _ = specs["pos0/moe/router"]
    assert all(s is None for s in rspec)


def test_embed_vocab_sharded():
    specs = _specs(get_config("olmo-1b"))
    spec, shape = specs["embed/e"]
    assert spec[0] == "model" and shape[0] == 50304


def test_mamba_dinner_sharded():
    specs = _specs(get_config("falcon-mamba-7b"))
    spec, shape = specs["pos0/mamba/conv_w"]
    assert spec[-1] == "model"
    spec, shape = specs["pos0/mamba/a_log"]
    assert spec[1] == "model"
    spec, _ = specs["pos0/mamba/in_proj/u"]   # out-shard
    assert spec[1] == "model"
    spec, _ = specs["pos0/mamba/out_proj/u"]  # in-shard
    assert spec[2] == "model"


def test_norms_replicated():
    specs = _specs(get_config("olmo-1b"))
    spec, _ = specs["final_norm/g"] if "final_norm/g" in specs else (P(), ())
    assert all(s is None for s in spec)


def test_batch_and_cache_shardings_build():
    """batch/cache sharding builders run against a concrete 1-device
    mesh (structure check only)."""
    from repro.launch.sharding import batch_shardings, cache_shardings
    from repro.models.lm import init_decode_cache
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = smoke_config("qwen3-4b")
    batch = {"tokens": jax.ShapeDtypeStruct((4, 8), jnp.int32),
             "cache_len": jax.ShapeDtypeStruct((), jnp.int32)}
    bs = batch_shardings(mesh, batch)
    assert len(jax.tree.leaves(bs)) == 2
    cache = jax.eval_shape(lambda: init_decode_cache(cfg, 4, 8))
    cs = cache_shardings(mesh, cache, 4)
    assert jax.tree.structure(cs) == jax.tree.structure(cache)
