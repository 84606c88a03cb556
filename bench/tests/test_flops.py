"""The FLOP and byte functions against hand counts at toy shapes."""

from bench import flops, traffic

TOY = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
       "head_dim": 2, "intermediate_size": 6, "vocab_size": 10,
       "num_hidden_layers": 3}
PEAKS = {"bf16_flops": 100.0, "hbm_bytes_s": 10.0}


def test_linear_params_by_hand():
    # q 4x4, k 4x2, v 4x2, o 4x4, gate/up/down 4x6 each
    assert flops.linear_params(TOY) == 16 + 8 + 8 + 16 + 3 * 24


def test_dense_flops_per_token_by_hand():
    assert flops.dense_flops_per_token(TOY) == 2 * (3 * 120 + 10 * 4)


def test_attn_flops_by_hand():
    # q.k and p.v: 2 + 2 FLOPs per head dim, per head, per layer, per key
    assert flops.attn_flops(TOY, 5) == 4 * 3 * 2 * 2 * 5


def test_serve_model_flops_sums_each_position():
    per = flops.dense_flops_per_token(TOY)
    # a request of 3 positions attends to 1, 2 and 3 keys
    want = 3 * per + flops.attn_flops(TOY, 1 + 2 + 3)
    assert flops.serve_model_flops(TOY, [(0, 3)]) == want
    assert flops.serve_model_flops(TOY, [(0, 3), (0, 3)]) == 2 * want
    # positions 3 and 4 of a request attend to 4 and 5 keys
    assert flops.serve_model_flops(TOY, [(3, 5)]) == (
        2 * per + flops.attn_flops(TOY, 4 + 5))


def test_paged_bytes_by_hand():
    # 2 rows x 3 pages x 4 tokens x a row of (1 head x 2 dims) x 2 bytes,
    # read and written
    assert flops.paged_gather_bytes(2, 3, 4, 1 * 2 * 2) == (
        2 * 2 * 3 * 4 * 2 * 2)
    assert flops.paged_scatter_bytes(5, 1 * 2 * 2) == 2 * 5 * 2 * 2


def test_prefill_attn_cost_by_hand():
    # a view position of k and v rows of (1 head x 2 dims) x 2 bytes
    f, b = flops.prefill_attn_cost(b=1, c=2, h=2, dh=2, s=4,
                                   view_row_bytes=2 * 1 * 2 * 2, itemsize=2)
    assert f == 4 * 1 * 2 * 2 * 4 * 2
    assert b == 2 * (2 * 1 * 2 * 2 * 2 + 2 * 1 * 4 * 1 * 2)


def test_roofline_share_takes_the_longer_bound():
    share, bound = flops.roofline_share(100.0, 10.0, 2.0, PEAKS)
    assert (share, bound) == (50.0, "flops")
    share, bound = flops.roofline_share(0.0, 40.0, 8.0, PEAKS)
    assert (share, bound) == (50.0, "bytes")


def test_tokens_processed_drops_the_last_token():
    assert traffic.tokens_processed(7, 3) == 9
    assert traffic.tokens_processed(7, 0) == 7


def test_queue_sizes_are_the_same_for_every_seed_and_clipped():
    mix = {"queue_requests": 50, "layout_seed": 3, "page_size": 16,
           "max_pages_per_slot": 20,
           "prompt_len": {"dist": "lognormal", "median": 100, "sigma": 1.0,
                          "min": 20},
           "output_len": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                          "min": 4, "max": 30}}
    sizes = traffic.queue_sizes(mix)
    assert sizes == traffic.queue_sizes(dict(mix))
    assert min(p for p, _ in sizes) >= 20
    assert all(4 <= o <= 30 and p + o <= 320 for p, o in sizes)
    # the longest prompts are cut to the context less their answer
    assert any(p + o == 320 for p, o in sizes)
    ps = sorted(p for p, _ in sizes)
    assert ps[24] <= 100 <= ps[25]      # the median lies between the two
    a = traffic.queue_tokens(mix, 2 ** 33 + 5, 1000)
    b = traffic.queue_tokens(mix, 2 ** 33 + 6, 1000)
    assert [len(x) for x in a] == [len(x) for x in b]
    assert any((x != y).any() for x, y in zip(a, b))


# Moonlight-16B-A3B: the catalog's config of the published config.json, and
# the keys the published file holds besides (metadata and training-only
# settings; their values do not matter here), cut as its cell would be:
# one of three pipeline stages, 8 of the 64 routed experts on this chip
MOONLIGHT = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 11264,
    "kv_lora_rank": 512, "max_position_embeddings": 8192,
    "model_type": "deepseek_v3", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 8,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts_per_tok": 6, "num_hidden_layers": 9,
    "num_key_value_heads": 16, "num_nextn_predict_layers": 0,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_theta": 50000,
    "routed_scaling_factor": 2.446, "scoring_func": "sigmoid",
    "seq_aux": True, "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840,
    "architectures": ["DeepseekV3ForCausalLM"], "auto_map": {},
    "aux_loss_alpha": 0.001, "bos_token_id": 0, "eos_token_id": 1,
    "initializer_range": 0.02, "pretraining_tp": 1, "rope_scaling": None,
    "attention_dropout": 0.0, "torch_dtype": "bfloat16",
    "transformers_version": "4.0", "use_cache": True,
    "source": "https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/"
              "main/config.json",
    "arch": "moonshot-v1-16b-a3b", "reference": "moe_mla_decoder",
    "reduced": ["num_hidden_layers", "n_routed_experts"],
    "published": {"num_hidden_layers": 27, "n_routed_experts": 64},
    "assumed": {"norm": "rmsnorm", "qk_norm": False, "ptc_block": 128,
                "embed_scale": 0.0625}}


def test_moonlight_flops_by_hand():
    # MLA linears: q 2048 x 16·192, KV down 2048 x (512 + 64),
    # KV up 512 x 16·(128 + 128), o 16·128 x 2048
    attn = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    assert attn == 13_762_560
    assert flops.attn_linear_params(MOONLIGHT) == attn
    # layer 0 dense: gate, up, down of 2048 x 11264
    dense = 3 * 2048 * 11264
    assert flops.linear_params(MOONLIGHT, 0) == attn + dense
    # layers 1-8: the router over all 64 experts, 2 shared experts and
    # 6 x 8/64 = 0.75 routed ones in expectation, each 3 x 2048 x 1408
    moe = 2048 * 64 + 2.75 * 3 * 2048 * 1408
    assert moe == 131_072 + 23_789_568
    assert flops.linear_params(MOONLIGHT, 1) == attn + moe
    assert flops.dense_flops_per_token(MOONLIGHT) == 2 * (
        9 * attn + dense + 8 * moe + 163840 * 2048)
    # q.k over 128 + 64 dims and p.v over 128, 16 heads, 9 layers
    assert flops.attn_flops(MOONLIGHT, 5) == 2 * 9 * 16 * (192 + 128) * 5
    # with q_lora_rank set, q goes through it
    ranked = dict(MOONLIGHT, q_lora_rank=1536)
    assert flops.attn_linear_params(ranked) == (
        attn - 2048 * 3072 + 2048 * 1536 + 1536 * 3072)


def test_dense_cells_count_as_before():
    """The GQA count of both cells' files is the one the benchmark used
    before it took experts and latent attention: q, k, v, o and a gated
    MLP, and 4·h·dh of attention per query and position."""
    import json

    from bench.cell import BENCH

    for name in ("qwen3-4b", "olmo-1b"):
        cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        hkv, dh, ff = (cfg["num_key_value_heads"], cfg["head_dim"],
                       cfg["intermediate_size"])
        per_layer = d * h * dh + 2 * d * hkv * dh + h * dh * d + 3 * d * ff
        assert flops.dense_flops_per_token(cfg) == 2 * (
            cfg["num_hidden_layers"] * per_layer + cfg["vocab_size"] * d)
        assert flops.attn_flops(cfg, 7) == (
            4 * cfg["num_hidden_layers"] * h * dh * 7)
