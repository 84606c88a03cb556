"""Operations and bytes the algorithm needs, from shapes alone.

``cfg`` is a configuration file's dict (published keys).  A PTC linear
counts as its M x N W-equivalent, the count of ``active_param_count`` in
``benchmarks/roofline.py``: the per-call recomposition of W from its
factors and any padding of M or N to whole k-blocks are not counted.

Attention is grouped-query (GQA, MHA included) or, where the file states
``kv_lora_rank``, latent (MLA, DeepSeek-V2/V3 keys).  The feed-forward
block is a gated MLP of ``intermediate_size``, or, where the file states
``n_routed_experts``, sparse experts after the first
``first_k_dense_replace`` layers.
"""

from __future__ import annotations

import math

import numpy as np

__all__ = ["attn_linear_params", "ffn_params", "linear_params",
           "dense_flops_per_token", "attn_flops", "serve_model_flops",
           "pool_row_bytes", "paged_gather_bytes", "paged_scatter_bytes",
           "prefill_attn_cost", "roofline_share"]


def _head_dims(cfg: dict) -> tuple[int, int]:
    """(q.k width, value width) of one attention head."""
    if cfg.get("kv_lora_rank"):
        return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
                cfg["v_head_dim"])
    dh = cfg.get("head_dim") or cfg["hidden_size"] // cfg[
        "num_attention_heads"]
    return dh, dh


def attn_linear_params(cfg: dict) -> int:
    """W-equivalent parameters of one layer's attention linears.  GQA: q,
    k, v, o.  MLA: q (d x h·(nope + rope), or through ``q_lora_rank``
    where it is set), the joint down-projection of the latent KV and the
    rotary key d x (kv_lora + rope), the up-projection kv_lora x
    h·(nope + v), and o."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r = cfg.get("kv_lora_rank")
    if not r:
        dh, _ = _head_dims(cfg)
        return d * h * dh + 2 * d * cfg["num_key_value_heads"] * dh \
            + h * dh * d
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    qr = cfg.get("q_lora_rank")
    q = d * qr + qr * h * (nope + rope) if qr else d * h * (nope + rope)
    return q + d * (r + rope) + r * h * (nope + v) + h * v * d


def ffn_params(cfg: dict, layer: int) -> float:
    """W-equivalent parameters of layer ``layer``'s feed-forward block
    that one token runs through.  A gated MLP (gate, up, down) of
    ``intermediate_size`` in the first ``first_k_dense_replace`` layers,
    or with no experts.  Else the router, d x the published expert
    count (it scores every expert, held here or not), and gated experts
    of ``moe_intermediate_size``: the shared ones, and of the token's
    ``num_experts_per_tok`` routed ones the share held on this chip,
    top_k x held / published in expectation (``held`` is the file's
    ``n_routed_experts``, ``published`` the count under ``published``)."""
    d = cfg["hidden_size"]
    held = cfg.get("n_routed_experts") or 0
    if not held or layer < (cfg.get("first_k_dense_replace") or 0):
        return 3 * d * cfg["intermediate_size"]
    published = cfg.get("published", {}).get("n_routed_experts", held)
    experts = (cfg["num_experts_per_tok"] * held / published
               + (cfg.get("n_shared_experts") or 0))
    return d * published + experts * 3 * d * cfg["moe_intermediate_size"]


def linear_params(cfg: dict, layer: int = 0) -> float:
    """W-equivalent parameters of layer ``layer``'s linears that one
    token runs through: attention and the feed-forward block."""
    return attn_linear_params(cfg) + ffn_params(cfg, layer)


def dense_flops_per_token(cfg: dict) -> float:
    """2 x (all layers' linears + the unembedding) for one position."""
    return 2 * (sum(linear_params(cfg, i)
                    for i in range(cfg["num_hidden_layers"]))
                + cfg["vocab_size"] * cfg["hidden_size"])


def attn_flops(cfg: dict, context: int) -> int:
    """q.k and p.v over ``context`` positions, all layers, one query:
    2·h·(qk width + value width) per layer and position."""
    qk, v = _head_dims(cfg)
    return (2 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * (qk + v) * context)


def serve_model_flops(cfg: dict, spans: list[tuple[int, int]]) -> float:
    """Model FLOPs of requests that each ran positions p0 .. p1 - 1
    through the model, position t attending to t + 1 positions."""
    per_tok = dense_flops_per_token(cfg)
    return sum((p1 - p0) * per_tok
               + attn_flops(cfg, (p1 * (p1 + 1) - p0 * (p0 + 1)) // 2)
               for p0, p1 in spans)


def pool_row_bytes(pool: dict) -> list[int]:
    """Bytes of one row (one cached position of one layer) of each tensor
    of a KV pool ``{position: {tensor: (row shape, dtype)}}``."""
    return [math.prod(row) * np.dtype(dtype).itemsize
            for tensors in pool.values() for row, dtype in tensors.values()]


def paged_gather_bytes(rows: int, pages_per_row: int, page_size: int,
                       row_bytes: int) -> int:
    """Pages read plus view written, one gather call; ``row_bytes`` is
    one cached position of one layer."""
    return 2 * rows * pages_per_row * page_size * row_bytes


def paged_scatter_bytes(rows: int, row_bytes: int) -> int:
    """New rows read plus the same rows written into the pool."""
    return 2 * rows * row_bytes


def prefill_attn_cost(b: int, c: int, h: int, dh: int, s: int,
                      view_row_bytes: int, itemsize: int) -> tuple[int, int]:
    """(FLOPs, bytes) of one prefill-attention call: every query of the
    (B, C) chunk against the whole (B, S) view, as the kernel's grid
    walks it; q read and output written (``itemsize`` a value), the
    views read (``view_row_bytes`` a position, K and V together)."""
    flops = 4 * b * h * c * s * dh
    nbytes = itemsize * 2 * b * c * h * dh + b * s * view_row_bytes
    return flops, nbytes


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict) -> tuple[float, str]:
    """Percent of the chip's roofline and the bound that sets it: the
    least time (FLOPs over peak FLOP/s or bytes over peak bytes/s,
    whichever is longer) over the measured time."""
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_s"]
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
