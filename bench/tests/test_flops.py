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
    # 2 rows x 3 pages x 4 tokens x (1 head x 2 dims) x 2 bytes, read+write
    assert flops.paged_gather_bytes(2, 3, 4, 1, 2, 2) == 2 * 2 * 3 * 4 * 2 * 2
    assert flops.paged_scatter_bytes(5, 1, 2, 2) == 2 * 5 * 2 * 2


def test_prefill_attn_cost_by_hand():
    f, b = flops.prefill_attn_cost(b=1, c=2, h=2, hkv=1, dh=2, s=4,
                                   itemsize=2)
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
