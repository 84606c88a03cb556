"""Operations and bytes the algorithm needs, from shapes alone.

``cfg`` is a configuration file's dict (published keys).  A PTC linear
counts as its M x N W-equivalent, the count of ``active_param_count`` in
``benchmarks/roofline.py``: the per-call recomposition of W from its
factors and any padding of M or N to whole k-blocks are not counted.
"""

from __future__ import annotations

__all__ = ["linear_params", "dense_flops_per_token", "attn_flops",
           "serve_model_flops", "paged_gather_bytes", "paged_scatter_bytes",
           "prefill_attn_cost", "roofline_share"]


def _dims(cfg: dict) -> tuple[int, int, int, int, int]:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hkv = cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // h
    return d, h, hkv, dh, cfg["intermediate_size"]


def linear_params(cfg: dict) -> int:
    """W-equivalent parameters of one layer's linears: q, k, v, o and a
    gated MLP (gate, up, down)."""
    d, h, hkv, dh, ff = _dims(cfg)
    return d * h * dh + 2 * d * hkv * dh + h * dh * d + 3 * d * ff


def dense_flops_per_token(cfg: dict) -> int:
    """2 x (all layers' linears + the unembedding) for one position."""
    return 2 * (cfg["num_hidden_layers"] * linear_params(cfg)
                + cfg["vocab_size"] * cfg["hidden_size"])


def attn_flops(cfg: dict, context: int) -> int:
    """q.k and p.v over ``context`` positions, all layers, one query."""
    _, h, _, dh, _ = _dims(cfg)
    return 4 * cfg["num_hidden_layers"] * h * dh * context


def serve_model_flops(cfg: dict, spans: list[tuple[int, int]]) -> int:
    """Model FLOPs of requests that each ran positions p0 .. p1 - 1
    through the model, position t attending to t + 1 positions."""
    per_tok = dense_flops_per_token(cfg)
    return sum((p1 - p0) * per_tok
               + attn_flops(cfg, (p1 * (p1 + 1) - p0 * (p0 + 1)) // 2)
               for p0, p1 in spans)


def paged_gather_bytes(rows: int, pages_per_row: int, page_size: int,
                       hkv: int, dh: int, itemsize: int) -> int:
    """Pages read plus view written, one gather call."""
    return 2 * rows * pages_per_row * page_size * hkv * dh * itemsize


def paged_scatter_bytes(rows: int, hkv: int, dh: int, itemsize: int) -> int:
    """New rows read plus the same rows written into the pool."""
    return 2 * rows * hkv * dh * itemsize


def prefill_attn_cost(b: int, c: int, h: int, hkv: int, dh: int, s: int,
                      itemsize: int) -> tuple[int, int]:
    """(FLOPs, bytes) of one prefill-attention call: every query of the
    (B, C) chunk against the whole (B, S) view, as the kernel's grid
    walks it; q read, output written, K and V views read."""
    flops = 4 * b * h * c * s * dh
    nbytes = itemsize * (2 * b * c * h * dh + 2 * b * s * hkv * dh)
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
