"""Model assembly: decoder-only / enc-dec / VLM LMs from PTC layers.

Architectures are described by :class:`ArchConfig` and composed as
``n_periods`` repetitions of a static *period plan* — a short list of
sub-layers (attn / mamba, each with mlp / moe) — so heterogeneous stacks
(gemma2's local/global alternation, jamba's 1-attn:7-mamba interleave
with MoE every other layer, llama-vision's cross-attn every 5th layer)
still scan as homogeneous ``lax.scan`` stacks: per-position parameters
are stacked over the period axis and sliced inside the scan body.

The paper's multi-level sparsity is first-class here: ``inject_masks``
adds per-step feedback/column masks as leaves *inside* the PTC param
dicts (so scan slicing distributes them layer-wise automatically) and
``apply_ptc_linear`` picks them up — the in-situ custom_vjp then
computes exactly the sampled estimator the photonic chip would.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from ..core.sparsity import SparsityConfig, feedback_mask, column_mask
from .layers import (PTCLinearCfg,                      init_rmsnorm, rmsnorm, init_layernorm, layernorm,
                     layernorm_np, init_embedding, embed, softcap,
                     trainable_mask, partition, combine, maybe_constraint,
                     ptc_scope)
from .attention import (AttnCfg, init_attention, attention, decode_attention,
                        decode_attention_paged,
                        decode_attention_paged_chunked, init_kv_cache)
from .ffn import FFNCfg, MoECfg, init_mlp, mlp, init_moe, moe
from .ssm import SSMCfg, init_mamba, mamba, mamba_decode, init_ssm_state

__all__ = ["ArchConfig", "SubLayerPlan", "init_model", "forward",
           "build_train_step", "build_serve_step", "build_gateway_step",
           "build_gateway_prefill_step", "init_decode_cache",
           "model_trainable_mask", "inject_masks"]

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                     # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int | None = None
    # attention flavour
    rope_theta: float = 10000.0
    rope_frac: float = 1.0
    qk_norm: bool = False
    qkv_bias: bool = False
    attn_softcap: float | None = None
    final_softcap: float | None = None
    sliding_window: int | None = None
    local_global: bool = False      # gemma2: alternate local/global layers
    # moe
    n_experts: int = 0
    top_k: int = 0
    moe_period: int = 1             # MoE every `moe_period`-th sub-layer
    moe_dispatch: str = "pjit"      # pjit | a2a (shard_map all_to_all EP)
    # ssm / hybrid
    ssm_state: int = 0
    ssm_chunk: int = 256            # associative-scan chunk length
    attn_period: int = 0            # jamba: 1 attn per `attn_period` layers
    # enc-dec / vlm
    n_enc_layers: int = 0
    cross_attn_period: int = 0      # cross-attn every N-th layer
    n_img_tokens: int = 0
    # norms / activations / embeddings
    norm: str = "rmsnorm"           # rmsnorm | layernorm | nonparam
    act: str = "silu"
    post_norm: bool = False         # gemma2 sandwich norm
    tie_embed: bool = True
    # substrate policy
    ptc: PTCLinearCfg = dataclasses.field(default_factory=PTCLinearCfg)
    remat: bool = True
    remat_policy: str = "full"      # full | dots (save matmul outputs) |
    #                                 none — the memory/recompute knob
    attn_chunk: int | None = None   # chunked-softmax threshold (prefill)
    unroll: bool = False            # python-loop the stack instead of scan
    # (the roofline driver unrolls reduced-depth compiles: cost_analysis
    # counts a lax.scan body once, an unrolled stack exactly)

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.n_heads

    def attn_cfg(self, window=None, causal=True) -> AttnCfg:
        return AttnCfg(d_model=self.d_model, n_heads=self.n_heads,
                       n_kv_heads=self.n_kv_heads, head_dim=self.hd,
                       rope_theta=self.rope_theta, rope_frac=self.rope_frac,
                       qk_norm=self.qk_norm, attn_softcap=self.attn_softcap,
                       qkv_bias=self.qkv_bias, causal=causal, window=window)

    def moe_cfg(self) -> MoECfg:
        return MoECfg(d_model=self.d_model, d_ff=self.d_ff,
                      n_experts=self.n_experts, top_k=self.top_k,
                      act=self.act, dispatch=self.moe_dispatch)

    def ffn_cfg(self) -> FFNCfg:
        return FFNCfg(d_model=self.d_model, d_ff=self.d_ff, act=self.act)

    def ssm_cfg(self) -> SSMCfg:
        return SSMCfg(d_model=self.d_model, d_state=self.ssm_state,
                      chunk=self.ssm_chunk)


@dataclasses.dataclass(frozen=True)
class SubLayerPlan:
    kind: str                       # attn | mamba
    ffn: str                        # mlp | moe
    window: int | None = None
    cross: bool = False             # extra cross-attention block
    causal: bool = True             # False for encoder stacks


def period_plan(cfg: ArchConfig) -> tuple[list[SubLayerPlan], int]:
    """(plan, n_periods): the static per-period sub-layer schedule."""
    ffn = "moe" if (cfg.n_experts > 0 and cfg.attn_period == 0) else "mlp"
    if cfg.family == "encdec":
        # the DECODER stack (self-attn + cross-attn); encoder is separate
        return [SubLayerPlan("attn", ffn, cross=True)], cfg.n_layers
    if cfg.family in ("dense", "moe"):
        if cfg.local_global:
            plan = [SubLayerPlan("attn", ffn, window=cfg.sliding_window),
                    SubLayerPlan("attn", ffn, window=None)]
            assert cfg.n_layers % 2 == 0
            return plan, cfg.n_layers // 2
        return [SubLayerPlan("attn", ffn)], cfg.n_layers
    if cfg.family == "ssm":
        return [SubLayerPlan("mamba", "none")], cfg.n_layers
    if cfg.family == "hybrid":
        # jamba: period of `attn_period` layers — 1 attention + rest mamba,
        # MoE on every `moe_period`-th position
        ap = cfg.attn_period
        plan = []
        for i in range(ap):
            kind = "attn" if i == 0 else "mamba"
            f = "moe" if (cfg.n_experts and i % cfg.moe_period == 1) else "mlp"
            plan.append(SubLayerPlan(kind, f))
        assert cfg.n_layers % ap == 0
        return plan, cfg.n_layers // ap
    if cfg.family == "vlm":
        cp = cfg.cross_attn_period
        plan = [SubLayerPlan("attn", "mlp", cross=(i == cp - 1))
                for i in range(cp)]
        assert cfg.n_layers % cp == 0
        return plan, cfg.n_layers // cp
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_norm(cfg: ArchConfig) -> Params:
    if cfg.norm == "rmsnorm":
        return init_rmsnorm(cfg.d_model)
    if cfg.norm == "layernorm":
        return init_layernorm(cfg.d_model)
    return {}   # nonparam


def _apply_norm(cfg: ArchConfig, p: Params, x: jax.Array) -> jax.Array:
    if cfg.norm == "rmsnorm":
        return rmsnorm(p, x)
    if cfg.norm == "layernorm":
        return layernorm(p, x)
    return layernorm_np(x)


def _init_sublayer(key: jax.Array, cfg: ArchConfig, plan: SubLayerPlan
                   ) -> Params:
    ks = jax.random.split(key, 8)
    p: Params = {"ln1": _init_norm(cfg)}
    if plan.kind == "attn":
        p["attn"] = init_attention(ks[0], cfg.attn_cfg(plan.window), cfg.ptc)
    else:
        p["mamba"] = init_mamba(ks[0], cfg.ssm_cfg(), cfg.ptc)
    if cfg.post_norm:
        p["pn1"] = _init_norm(cfg)
    if plan.cross:
        p["lnx"] = _init_norm(cfg)
        p["cross"] = init_attention(
            ks[1], cfg.attn_cfg(causal=False), cfg.ptc)
    if plan.ffn != "none":
        p["ln2"] = _init_norm(cfg)
        if plan.ffn == "moe":
            p["moe"] = init_moe(ks[2], cfg.moe_cfg(), cfg.ptc)
        else:
            p["mlp"] = init_mlp(ks[2], cfg.ffn_cfg(), cfg.ptc)
        if cfg.post_norm:
            p["pn2"] = _init_norm(cfg)
    return p


def init_model(key: jax.Array, cfg: ArchConfig) -> Params:
    plan, n_periods = period_plan(cfg)
    keys = jax.random.split(key, len(plan) + 4)
    params: Params = {
        "embed": init_embedding(keys[0], cfg.vocab, cfg.d_model,
                                cfg.ptc.base_dtype),
        "final_norm": _init_norm(cfg),
    }
    if not cfg.tie_embed:
        params["unembed"] = {
            "w": (jax.random.normal(keys[1], (cfg.vocab, cfg.d_model),
                                    jnp.float32)
                  * (cfg.d_model ** -0.5)).astype(cfg.ptc.base_dtype)}
    for i, sub in enumerate(plan):
        pk = jax.random.split(keys[2 + i], n_periods)
        params[f"pos{i}"] = jax.vmap(
            lambda k: _init_sublayer(k, cfg, sub))(pk)
    if cfg.family == "encdec":
        ek = jax.random.split(keys[-1], cfg.n_enc_layers)
        enc_plan = SubLayerPlan("attn", "mlp", causal=False)
        params["enc"] = jax.vmap(
            lambda k: _init_sublayer(k, cfg, enc_plan))(ek)
        params["enc_norm"] = _init_norm(cfg)
    return params


def model_trainable_mask(params: Params) -> Params:
    return trainable_mask(params)


# ---------------------------------------------------------------------------
# sampling-mask injection (paper §3.4.2, LM-scale)
# ---------------------------------------------------------------------------


def inject_masks(params: Params, key: jax.Array, scfg: SparsityConfig,
                 n_tokens: int) -> Params:
    """Return a copy of ``params`` with per-PTC ``fb``/``col`` mask leaves.

    Masks are sampled from stop-gradient block energies; stacked leading
    axes (period, experts, …) are vmapped over so scan/vmap slicing
    distributes the right mask to the right physical block grid."""
    if not scfg.enabled:
        return params
    counter = [0]

    def walk(p):
        if isinstance(p, dict):
            if "u" in p and "s" in p and "v" in p:
                out = dict(p)
                s = jax.lax.stop_gradient(p["s"]).astype(jnp.float32)
                energy = jnp.sum(s * s, axis=-1)        # (..., P, Q)
                k = jax.random.fold_in(key, counter[0])
                counter[0] += 1
                lead = energy.shape[:-2]
                if scfg.alpha_w < 1.0:
                    e2 = energy.reshape((-1,) + energy.shape[-2:])
                    ks = jax.random.split(k, e2.shape[0])
                    fb = jax.vmap(lambda kk, ee: feedback_mask(kk, ee, scfg)
                                  )(ks, e2)
                    out["fb"] = fb.reshape(lead + fb.shape[1:])
                if scfg.alpha_c < 1.0:
                    kc = jax.random.fold_in(k, 1)
                    if lead:
                        kcs = jax.random.split(kc, math.prod(lead))
                        col = jax.vmap(lambda kk: column_mask(
                            kk, n_tokens, scfg))(kcs)
                        out["col"] = col.reshape(lead + (n_tokens,))
                    else:
                        out["col"] = column_mask(kc, n_tokens, scfg)
                return out
            return {k2: walk(v) for k2, v in p.items()}
        return p

    return walk(params)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _sublayer_fwd(cfg: ArchConfig, plan: SubLayerPlan, p: Params, x, positions,
                  cross_kv=None):
    """One sub-layer (train/prefill path).  Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = _apply_norm(cfg, p["ln1"], x)
    if plan.kind == "attn":
        h = attention(p["attn"], cfg.attn_cfg(plan.window, plan.causal),
                      cfg.ptc, h, positions, chunk=cfg.attn_chunk)
    else:
        h = mamba(p["mamba"], cfg.ssm_cfg(), cfg.ptc, h)
    if cfg.post_norm:
        h = _apply_norm(cfg, p["pn1"], h)
    x = x + h
    if plan.cross:
        h = _apply_norm(cfg, p["lnx"], x)
        h = attention(p["cross"], cfg.attn_cfg(causal=False), cfg.ptc, h,
                      None, kv_x=cross_kv)
        x = x + h
    if plan.ffn != "none":
        h = _apply_norm(cfg, p["ln2"], x)
        if plan.ffn == "moe":
            h, a = moe(p["moe"], cfg.moe_cfg(), cfg.ptc, h)
            aux = aux + a
        else:
            h = mlp(p["mlp"], cfg.ffn_cfg(), cfg.ptc, h)
        if cfg.post_norm:
            h = _apply_norm(cfg, p["pn2"], h)
        x = x + h
    return x, aux


def _run_stack(cfg: ArchConfig, plan, stacked: list[Params], x, positions,
               cross_kv=None):
    """Scan the period stack.  ``stacked[i]`` has leading period axis."""
    def body(carry, layer_params):
        x, aux = carry
        for i, sub in enumerate(plan):
            x, a = _sublayer_fwd(cfg, sub, layer_params[i], x, positions,
                                 cross_kv)
            aux = aux + a
        return (x, aux), None

    if cfg.remat and cfg.remat_policy != "none":
        policy = None
        if cfg.remat_policy == "dots":
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        body = jax.checkpoint(body, policy=policy)
    if cfg.unroll:
        n_periods = jax.tree.leaves(stacked[0])[0].shape[0]
        carry = (x, jnp.zeros((), jnp.float32))
        for pi in range(n_periods):
            layer = [jax.tree.map(lambda a: a[pi], st) for st in stacked]
            carry, _ = body(carry, layer)
        return carry
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               stacked)
    return x, aux


def forward(params: Params, cfg: ArchConfig, batch: dict[str, jax.Array],
            ) -> tuple[jax.Array, jax.Array]:
    """Token logits for a full sequence.  Returns (logits, aux_loss)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = embed(params["embed"], tokens)
    if cfg.family != "ssm":
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)

    cross_kv = None
    if cfg.family == "encdec":
        enc = batch["frames"].astype(x.dtype)       # stubbed audio frontend
        enc_pos = jnp.broadcast_to(jnp.arange(enc.shape[1])[None],
                                   (b, enc.shape[1]))
        enc_out, _ = _run_stack(
            cfg, [SubLayerPlan("attn", "mlp", causal=False)],
            [params["enc"]], enc, enc_pos)
        cross_kv = _apply_norm(cfg, params["enc_norm"], enc_out)
    if cfg.family == "vlm":
        cross_kv = batch["img"].astype(x.dtype)     # stubbed vision tower

    plan, _ = period_plan(cfg)
    stacked = [params[f"pos{i}"] for i in range(len(plan))]
    x, aux = _run_stack(cfg, plan, stacked, x, positions, cross_kv)
    x = _apply_norm(cfg, params["final_norm"], x)
    if cfg.tie_embed:
        logits = x @ params["embed"]["e"].T
    else:
        logits = x @ params["unembed"]["w"].T
    # keep the (B, S, vocab) logits vocab-sharded — replicated logits are
    # ~20 GB/device at 152k vocab (measured); CE reduces over the shard
    logits = maybe_constraint(logits, "dp", None, "model")
    logits = softcap(logits, cfg.final_softcap)
    return logits, aux


@jax.custom_vjp
def _ce(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Memory-lean softmax CE: the (B, S, V) tensor is never upcast to
    f32 (only the reduced max/denoms are) and the backward materializes
    a single bf16 softmax instead of f32 logit copies — at 256k vocab
    this is ~8 GB/device less live memory than the naive form."""
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    p = jnp.exp(logits - m)                       # stays in logits dtype
    denom = jnp.sum(p.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None],
                               axis=-1)[..., 0].astype(jnp.float32)
    lse = m[..., 0].astype(jnp.float32) + jnp.log(denom)
    return jnp.mean(lse - gold)


def _ce_fwd(logits, labels):
    return _ce(logits, labels), (logits, labels)


def _ce_bwd(res, g):
    logits, labels = res
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    denom = jnp.sum(p.astype(jnp.float32), axis=-1, keepdims=True)
    soft = (p / denom.astype(p.dtype))
    onehot = (labels[..., None] == jnp.arange(
        logits.shape[-1], dtype=labels.dtype)).astype(soft.dtype)
    n = 1
    for d in labels.shape:
        n *= d
    dl = (soft - onehot) * jnp.asarray(g / n, soft.dtype)
    return dl, None


_ce.defvjp(_ce_fwd, _ce_bwd)


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return _ce(logits, labels)


def build_train_step(cfg: ArchConfig, sparsity: SparsityConfig | None = None):
    """Returns train_step(params, batch, key) → (loss, grads).

    Gradients are taken ONLY w.r.t. the trainable partition (Σ +
    electronics); frozen U/V bases ride along as non-differentiated
    constants, so no zero-grad accumulators are ever materialized.
    Frozen positions in the returned grads tree are scalar-zero
    placeholders (the optimizer skips them via the same mask)."""
    scfg = sparsity

    def loss_fn(tr, fr, mask, batch, key):
        params = combine(tr, fr, mask)
        if scfg is not None and scfg.enabled:
            n_tokens = batch["tokens"].shape[0] * batch["tokens"].shape[1]
            params = inject_masks(params, key, scfg, n_tokens)
        logits, aux = forward(params, cfg, batch)
        return cross_entropy(logits, batch["labels"]) + aux

    def train_step(params, batch, key):
        mask = trainable_mask(params)
        tr, fr = partition(params, mask)
        loss, grads = jax.value_and_grad(loss_fn)(tr, fr, mask, batch, key)
        return loss, grads

    return train_step


# ---------------------------------------------------------------------------
# serve (decode) path
# ---------------------------------------------------------------------------


def init_decode_cache(cfg: ArchConfig, batch: int, max_len: int) -> Params:
    plan, n_periods = period_plan(cfg)
    cache: Params = {}
    for i, sub in enumerate(plan):
        if sub.kind == "attn":
            one = init_kv_cache(batch, max_len, cfg.attn_cfg(sub.window))
        else:
            one = init_ssm_state(batch, cfg.ssm_cfg())
        cache[f"pos{i}"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_periods,) + a.shape), one)
    return cache


def build_serve_step(cfg: ArchConfig):
    """Returns serve_step(params, cache, batch) → (logits, new_cache).

    ``batch``: {"token": (B,1) int32, "cache_len": () int32,
    ["img"/"frames" for vlm/encdec]}.  One new token against a KV cache
    of length ``cache_len`` (the decode_* / long_* dry-run shapes)."""
    plan, n_periods = period_plan(cfg)

    def serve_step(params, cache, batch):
        tok = batch["token"]
        b = tok.shape[0]
        cache_len = batch["cache_len"]
        x = embed(params["embed"], tok)
        if cfg.family != "ssm":
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        cross_kv = None
        if cfg.family == "vlm":
            cross_kv = batch["img"].astype(x.dtype)
        if cfg.family == "encdec":
            cross_kv = batch["enc_out"].astype(x.dtype)

        def body(x, per):
            # PTC layers are name-scoped (``p{period}.s{sublayer}.<module>``)
            # so the hardware-in-the-loop executor (models.layers.
            # ptc_execution) can key tenant placement on a stable layer id;
            # under lax.scan the scopes only run at trace time and the hook
            # stays inert (tracer guard), so naming costs nothing there.
            layer_params, layer_cache = per
            new_cache = {}
            for i, sub in enumerate(plan):
                p = layer_params[f"pos{i}"]
                c = layer_cache[f"pos{i}"]
                h = _apply_norm(cfg, p["ln1"], x)
                if sub.kind == "attn":
                    with ptc_scope(f"s{i}.attn"):
                        h, c = decode_attention(p["attn"],
                                                cfg.attn_cfg(sub.window),
                                                cfg.ptc, h, c, cache_len)
                else:
                    with ptc_scope(f"s{i}.mamba"):
                        h, c = mamba_decode(p["mamba"], cfg.ssm_cfg(),
                                            cfg.ptc, h, c)
                if cfg.post_norm:
                    h = _apply_norm(cfg, p["pn1"], h)
                x = x + h
                if sub.cross:
                    h = _apply_norm(cfg, p["lnx"], x)
                    with ptc_scope(f"s{i}.cross"):
                        h = attention(p["cross"], cfg.attn_cfg(causal=False),
                                      cfg.ptc, h, None, kv_x=cross_kv)
                    x = x + h
                if sub.ffn != "none":
                    h = _apply_norm(cfg, p["ln2"], x)
                    if sub.ffn == "moe":
                        h, _ = moe(p["moe"], cfg.moe_cfg(), cfg.ptc, h)
                    else:
                        with ptc_scope(f"s{i}.mlp"):
                            h = mlp(p["mlp"], cfg.ffn_cfg(), cfg.ptc, h)
                    if cfg.post_norm:
                        h = _apply_norm(cfg, p["pn2"], h)
                    x = x + h
                new_cache[f"pos{i}"] = c
            return x, new_cache

        layer_stack = {f"pos{i}": params[f"pos{i}"] for i in range(len(plan))}
        if cfg.unroll:
            outs = []
            for pi in range(n_periods):
                lp = jax.tree.map(lambda a: a[pi], layer_stack)
                lc = jax.tree.map(lambda a: a[pi], cache)
                with ptc_scope(f"p{pi}"):
                    x, c = body(x, (lp, lc))
                outs.append(c)
            new_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        else:
            x, new_cache = jax.lax.scan(body, x, (layer_stack, cache))
        x = _apply_norm(cfg, params["final_norm"], x)
        if cfg.tie_embed:
            logits = x @ params["embed"]["e"].T
        else:
            logits = x @ params["unembed"]["w"].T
        return softcap(logits, cfg.final_softcap)[:, 0], new_cache

    return serve_step


def build_gateway_step(cfg: ArchConfig):
    """Returns gateway_step(params, views, batch) → (logits, new_kv):
    the continuous-batching decode step over *page-assembled* KV views
    with per-sequence cache lengths (``repro.serving.engine``).

    ``batch``: {"token": (B, 1) int32, "lens": (B,) int32} — B is the
    gateway's slot count, each slot at its own decode position.
    ``views`` mirrors :func:`init_decode_cache`'s tree: per sub-layer
    position either ``{"k","v"}`` views (n_periods, B, S_max, Hkv, Dh)
    gathered from the page pool, or an SSM state.  Unlike the dense
    serve step the views are step-scratch: the returned ``new_kv``
    holds only each attention layer's NEW (n_periods, B, 1, Hkv, Dh)
    rows (the engine scatters them into the pool) plus full replacement
    SSM states.

    PTC scope names are identical to :func:`build_serve_step`'s
    (``p{period}.s{sub}.attn.wq`` …), so a hardware-in-the-loop
    deployment recorded off the solo serve path routes the gateway's
    coalesced frames onto the same tenants."""
    plan, n_periods = period_plan(cfg)
    if cfg.family in ("vlm", "encdec"):
        raise ValueError(
            f"gateway decode does not support {cfg.family} archs "
            f"(per-request cross-attention streams are not paged yet)")
    if cfg.n_experts > 0:
        raise ValueError("gateway decode does not support MoE archs yet")

    def gateway_step(params, views, batch):
        tok = batch["token"]
        lens = batch["lens"]
        x = embed(params["embed"], tok)
        if cfg.family != "ssm":
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)

        def body(x, per):
            layer_params, layer_views = per
            new = {}
            for i, sub in enumerate(plan):
                p = layer_params[f"pos{i}"]
                c = layer_views[f"pos{i}"]
                h = _apply_norm(cfg, p["ln1"], x)
                if sub.kind == "attn":
                    with ptc_scope(f"s{i}.attn"):
                        h, k_new, v_new = decode_attention_paged(
                            p["attn"], cfg.attn_cfg(sub.window), cfg.ptc,
                            h, c["k"], c["v"], lens)
                    new[f"pos{i}"] = {"k": k_new, "v": v_new}
                else:
                    with ptc_scope(f"s{i}.mamba"):
                        h, st = mamba_decode(p["mamba"], cfg.ssm_cfg(),
                                             cfg.ptc, h, c)
                    new[f"pos{i}"] = st
                if cfg.post_norm:
                    h = _apply_norm(cfg, p["pn1"], h)
                x = x + h
                if sub.ffn != "none":
                    h = _apply_norm(cfg, p["ln2"], x)
                    with ptc_scope(f"s{i}.mlp"):
                        h = mlp(p["mlp"], cfg.ffn_cfg(), cfg.ptc, h)
                    if cfg.post_norm:
                        h = _apply_norm(cfg, p["pn2"], h)
                    x = x + h
            return x, new

        layer_stack = {f"pos{i}": params[f"pos{i}"] for i in range(len(plan))}
        if cfg.unroll:
            outs = []
            for pi in range(n_periods):
                lp = jax.tree.map(lambda a: a[pi], layer_stack)
                lv = jax.tree.map(lambda a: a[pi], views)
                with ptc_scope(f"p{pi}"):
                    x, nk = body(x, (lp, lv))
                outs.append(nk)
            new_kv = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        else:
            x, new_kv = jax.lax.scan(body, x, (layer_stack, views))
        x = _apply_norm(cfg, params["final_norm"], x)
        if cfg.tie_embed:
            logits = x @ params["embed"]["e"].T
        else:
            logits = x @ params["unembed"]["w"].T
        return softcap(logits, cfg.final_softcap)[:, 0], new_kv

    return gateway_step


def build_gateway_prefill_step(cfg: ArchConfig):
    """Returns prefill_step(params, views, batch) → (logits, new_kv):
    the chunked-prefill gateway step — every slot advances up to C
    tokens per call instead of one.

    ``batch``: {"token": (B, C) int32, "lens": (B,) int32,
    "n_valid": (B,) int32} — slot b's next ``n_valid[b]`` tokens sit in
    columns 0..n_valid-1 at absolute positions ``lens[b] + c`` (decode
    slots ride along with n_valid == 1; padding columns are arbitrary
    and masked).  ``views`` is :func:`build_gateway_step`'s tree; the
    returned ``new_kv`` holds (n_periods, B, C, Hkv, Dh) rows per
    attention position, of which the engine scatters the first
    ``n_valid[b]`` per slot.  Logits are taken at column
    ``n_valid[b]-1`` — the prediction after the slot's last real token
    — so the return shape matches the one-token step: (B, vocab).

    PTC scope names are IDENTICAL to :func:`build_gateway_step`
    (``p{period}.s{sub}.attn.wq`` …): a hardware deployment recorded
    off the solo serve path routes the wide (B·C-column) prefill frames
    onto the same tenants untouched.  The Pallas kernel derives its KV
    block from the shapes.

    Attention-only: ssm/hybrid recurrences are inherently sequential in
    tokens, and vlm/encdec/MoE are not paged at all — those archs keep
    the one-token path."""
    plan, n_periods = period_plan(cfg)
    if cfg.family in ("vlm", "encdec"):
        raise ValueError(
            f"gateway decode does not support {cfg.family} archs "
            f"(per-request cross-attention streams are not paged yet)")
    if cfg.n_experts > 0:
        raise ValueError("gateway decode does not support MoE archs yet")
    if any(sub.kind != "attn" for sub in plan):
        raise ValueError(
            "chunked prefill supports attention-only archs; ssm/hybrid "
            "token recurrences are sequential — use prefill_chunk=1")

    def prefill_step(params, views, batch):
        tok = batch["token"]
        lens = batch["lens"]
        n_valid = batch["n_valid"].astype(jnp.int32)
        x = embed(params["embed"], tok)
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)

        def body(x, per):
            layer_params, layer_views = per
            new = {}
            for i, sub in enumerate(plan):
                p = layer_params[f"pos{i}"]
                c = layer_views[f"pos{i}"]
                h = _apply_norm(cfg, p["ln1"], x)
                with ptc_scope(f"s{i}.attn"):
                    h, k_new, v_new = decode_attention_paged_chunked(
                        p["attn"], cfg.attn_cfg(sub.window), cfg.ptc,
                        h, c["k"], c["v"], lens)
                new[f"pos{i}"] = {"k": k_new, "v": v_new}
                if cfg.post_norm:
                    h = _apply_norm(cfg, p["pn1"], h)
                x = x + h
                if sub.ffn != "none":
                    h = _apply_norm(cfg, p["ln2"], x)
                    with ptc_scope(f"s{i}.mlp"):
                        h = mlp(p["mlp"], cfg.ffn_cfg(), cfg.ptc, h)
                    if cfg.post_norm:
                        h = _apply_norm(cfg, p["pn2"], h)
                    x = x + h
            return x, new

        layer_stack = {f"pos{i}": params[f"pos{i}"] for i in range(len(plan))}
        if cfg.unroll:
            outs = []
            for pi in range(n_periods):
                lp = jax.tree.map(lambda a: a[pi], layer_stack)
                lv = jax.tree.map(lambda a: a[pi], views)
                with ptc_scope(f"p{pi}"):
                    x, nk = body(x, (lp, lv))
                outs.append(nk)
            new_kv = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        else:
            x, new_kv = jax.lax.scan(body, x, (layer_stack, views))
        x = _apply_norm(cfg, params["final_norm"], x)
        if cfg.tie_embed:
            logits = x @ params["embed"]["e"].T
        else:
            logits = x @ params["unembed"]["w"].T
        logits = softcap(logits, cfg.final_softcap)      # (B, C, V)
        last = jnp.take_along_axis(logits, (n_valid - 1)[:, None, None],
                                   axis=1)
        return last[:, 0], new_kv

    return prefill_step
