"""A configuration file to the program's arch, and the weights for it."""

import dataclasses
import json
import math
import re
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops
from bench.arch import KEYS_DIR, load_keys, program_arch
from bench.cell import BENCH
from bench.tests.test_flops import MOONLIGHT
from bench.weights import make_weights


def config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name, sizes, ptc_k", [
    ("qwen3-4b", dict(n_layers=9, d_model=2560, n_heads=32, n_kv_heads=8,
                      head_dim=128, d_ff=9728, vocab=151936,
                      rope_theta=1000000.0), 128),
    ("olmo-1b", dict(n_layers=16, d_model=2048, n_heads=16, n_kv_heads=16,
                     head_dim=128, d_ff=8192, vocab=50304,
                     rope_theta=10000.0), 128),
])
def test_program_arch_of_both_cells(name, sizes, ptc_k):
    """The program's named arch with the file's sizes and PTC block, as
    the benchmark has always run it."""
    from repro.configs import get_config

    base = get_config(name)
    want = dataclasses.replace(base, **sizes,
                               ptc=dataclasses.replace(base.ptc, k=ptc_k))
    assert program_arch(config(name)) == want


def refusals(cfg: dict, keys: dict | None = None) -> dict:
    """What ``program_arch`` refuses: {key or field: its reason}."""
    with pytest.raises(ValueError) as e:
        program_arch(cfg, keys)
    said = str(e.value).split("does not take ", 1)[1]
    return {re.split("[ =]", part, 1)[0]: part for part in said.split("; ")}


@pytest.fixture
def base_keys(tmp_path) -> dict:
    """The mapping of ``base.json`` alone: what the base refuses stays
    refused here, whatever mapping files later configurations add."""
    shutil.copy(KEYS_DIR / "base.json", tmp_path)
    return load_keys(tmp_path)


def left_to_the_arch(cfg: dict, keys: dict) -> set:
    """The fields of the program's arch for ``cfg`` that hold another
    value than the file's silence means and that no key it states sets:
    what ``program_arch`` names besides the keys it cannot take."""
    from repro.configs import get_config

    stated = set(cfg) | {f"{group}.{k}" for group, v in cfg.items()
                         if isinstance(v, dict) for k in v}
    set_by_keys = {field.partition(".")[0]
                   for key in stated & set(keys["keys"])
                   for field in keys["keys"][key]}
    base = get_config(cfg["arch"])
    out = set()
    for f in dataclasses.fields(base):
        if (f.name in set_by_keys or f.name in keys["policy"]
                or f.name in keys["unstated"]):
            continue
        default = f.default
        if f.default_factory is not dataclasses.MISSING:
            default = f.default_factory()
        if getattr(base, f.name) != keys["unset_must_be"].get(f.name,
                                                              default):
            out.add(f.name)
    return out


def test_program_arch_names_what_it_cannot_take(base_keys):
    cfg = dict(config("olmo-1b"), kv_lora_rank=512)
    assert refusals(cfg, base_keys) == {
        "kv_lora_rank": "kv_lora_rank (not mapped)"}
    cfg = dict(config("qwen3-4b"), rms_norm_eps=1e-5)
    assert refusals(cfg, base_keys) == {
        "rms_norm_eps": "rms_norm_eps=1e-05 (the program runs 1e-06)"}
    # a key of a group that is neither mapped nor inert
    cfg = dict(config("qwen3-4b"), rope_scaling={"type": "yarn"})
    assert list(refusals(cfg, base_keys)) == ["rope_scaling.type"]


def test_program_arch_refuses_what_the_file_leaves_to_the_arch(base_keys):
    """A file naming an arch with experts, but stating none, does not
    run the arch's experts: each such field is named, with the value the
    program's arch holds."""
    from repro.configs import get_config

    cfg = dict(config("olmo-1b"), arch="moonshot-v1-16b-a3b")
    said = refusals(cfg, base_keys)
    want = left_to_the_arch(cfg, base_keys)
    assert want and set(said) == want
    base = get_config(cfg["arch"])
    for name in want:
        assert said[name].startswith(
            f"{name}={getattr(base, name)!r} in the program's arch")


def test_head_dim_left_out_is_hidden_over_heads():
    """A file with no head_dim runs d_model / n_heads, as the published
    configurations mean it and as bench/flops.py counts it, not the
    arch's own head width."""
    cfg = {k: v for k, v in config("qwen3-4b").items() if k != "head_dim"}
    arch = program_arch(cfg)
    assert arch.head_dim is None and arch.hd == 2560 // 32
    assert flops._head_dims(cfg) == (arch.hd, arch.hd)


def test_moonlight_stops_at_the_mapping(base_keys):
    """Moonlight's published keys stop at the base mapping, which names
    every expert, routing and latent-attention key, the norm's epsilon
    (the base runs 1e-6) and the arch's fields that no key sets, and no
    key it takes or lists as inert."""
    said = refusals(MOONLIGHT, base_keys)
    assert set(said) == {
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "first_k_dense_replace", "moe_intermediate_size",
        "moe_layer_freq", "n_routed_experts", "n_shared_experts",
        "num_experts_per_tok", "n_group", "topk_group", "norm_topk_prob",
        "routed_scaling_factor", "scoring_func", "topk_method",
        "rms_norm_eps"} | left_to_the_arch(MOONLIGHT, base_keys)
    assert said["rms_norm_eps"] == (
        "rms_norm_eps=1e-05 (the program runs 1e-06)")


def test_a_later_mapping_file_takes_its_keys(tmp_path):
    """A configuration with other keys brings a mapping file of its own,
    beside the one there is: its keys then set the arch's fields."""
    shutil.copy(KEYS_DIR / "base.json", tmp_path)
    (tmp_path / "experts.json").write_text(json.dumps({"keys": {
        "n_routed_experts": {"n_experts": "int",
                             "family": {"value": "moe"}},
        "num_experts_per_tok": {"top_k": "int"}}}))
    keys = load_keys(tmp_path)
    cfg = dict(config("olmo-1b"), arch="moonshot-v1-16b-a3b",
               n_routed_experts=8, num_experts_per_tok=6)
    arch = program_arch(cfg, keys)
    assert (arch.family, arch.n_experts, arch.top_k) == ("moe", 8, 6)
    (tmp_path / "other.json").write_text(json.dumps(
        {"keys": {"num_experts_per_tok": {"top_k": "float"}}}))
    with pytest.raises(ValueError, match="num_experts_per_tok"):
        load_keys(tmp_path)


def test_a_later_mapping_file_takes_a_key_the_base_runs(tmp_path,
                                                      monkeypatch):
    """A file that maps ``rms_norm_eps`` lifts the base's guard on it:
    1e-05 then sets the field, and 1e-06, which qwen states, its
    default.  The field is a stand-in's, patched in for the program's
    arch; without the file the refusal is the base's, word for word."""
    import repro.configs
    from repro.models.lm import ArchConfig

    stand_in = dataclasses.make_dataclass(
        "StandIn", [("norm_eps", float, dataclasses.field(default=1e-6))],
        bases=(ArchConfig,), frozen=True)
    real = repro.configs.get_config
    monkeypatch.setattr(repro.configs, "get_config", lambda name: stand_in(
        **{f.name: getattr(real(name), f.name)
           for f in dataclasses.fields(ArchConfig)}))
    shutil.copy(KEYS_DIR / "base.json", tmp_path)
    eps = dict(config("qwen3-4b"), rms_norm_eps=1e-5)
    assert refusals(eps, load_keys(tmp_path)) == {
        "rms_norm_eps": "rms_norm_eps=1e-05 (the program runs 1e-06)"}
    (tmp_path / "norm.json").write_text(json.dumps(
        {"keys": {"rms_norm_eps": {"norm_eps": "float"}}}))
    keys = load_keys(tmp_path)
    assert program_arch(eps, keys).norm_eps == 1e-5
    as_run = program_arch(config("qwen3-4b"), keys)
    assert as_run.norm_eps == 1e-6
    assert as_run == program_arch(config("qwen3-4b"), load_keys())


def test_weights_fill_an_expert_layout():
    """Every leaf of an expert model's layout is drawn, the router by the
    default rule: normal x (its last axis)^-1/2."""
    from repro.configs import smoke_config
    from repro.models.lm import init_model

    arch = smoke_config("moonshot-v1-16b-a3b")
    key = jax.random.PRNGKey(5)
    layout = jax.eval_shape(lambda k: init_model(k, arch), key)
    params = make_weights(key, layout, arch.d_model, 0.0625)
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    assert got == jax.tree.map(lambda s: (s.shape, s.dtype), layout)
    paths, _ = jax.tree_util.tree_flatten_with_path(params)
    i, router = next((i, a) for i, (p, a) in enumerate(paths)
                     if p[-1].key == "router")
    want = jax.jit(lambda k: jax.random.normal(
        jax.random.fold_in(k, i), router.shape, jnp.float32)
        * router.shape[-1] ** -0.5)(key)
    np.testing.assert_array_equal(router, want)
    assert router.shape[-2:] == (arch.n_experts, arch.d_model)


def test_named_rules_draw_as_written():
    """A layout of the named leaves draws, bitwise, what each rule says,
    leaf i from fold_in(key, i); a rank-1 leaf of no rule is zero."""
    bf, f32 = jnp.bfloat16, jnp.float32
    layout = {"a": {"u": jax.ShapeDtypeStruct((2, 3, 4, 8, 8), bf),
                    "v": jax.ShapeDtypeStruct((2, 3, 4, 8, 8), bf),
                    "s": jax.ShapeDtypeStruct((2, 3, 4, 8), f32)},
              "b": jax.ShapeDtypeStruct((16,), f32),
              "bias": jax.ShapeDtypeStruct((16,), f32),
              "e": jax.ShapeDtypeStruct((32, 16), bf),
              "g": jax.ShapeDtypeStruct((16,), f32)}
    key = jax.random.PRNGKey(9)
    got = make_weights(key, layout, 16, 0.0625)
    s_scale = math.sqrt(2.0 / (3 * 8 + 4 * 8)) * math.sqrt(8)

    @jax.jit        # in one program, as make_weights draws them
    def want(key):
        k = [jax.random.fold_in(key, i) for i in range(7)]  # flatten order
        return {"a": {"s": (jax.random.normal(k[0], (2, 3, 4, 8), f32)
                            * s_scale).astype(f32),
                      "u": jax.random.normal(k[1], (2, 3, 4, 8, 8), bf)
                      * jnp.asarray(8 ** -0.5, bf),
                      "v": jax.random.normal(k[2], (2, 3, 4, 8, 8), bf)
                      * jnp.asarray(8 ** -0.5, bf)},
                "b": jnp.zeros((16,), f32), "bias": jnp.zeros((16,), f32),
                "e": (jax.random.normal(k[5], (32, 16), f32)
                      * (16 ** -0.5 * 0.0625)).astype(bf),
                "g": jnp.ones((16,), f32)}

    jax.tree.map(np.testing.assert_array_equal, got, want(key))


def test_weights_refuse_an_integer_leaf():
    layout = {"table": jax.ShapeDtypeStruct((4, 4), jnp.int32)}
    with pytest.raises(ValueError, match="table"):
        make_weights(jax.random.PRNGKey(0), layout, 4, 1.0)
