#!/usr/bin/env python3
"""Chip smoke: the serving gateway, the Σ-only train step and the
photonic twin, each driven once on one TPU at qwen3-4b's published
widths (d_model 2560, 32/8 heads of 128, d_ff 10240, vocab 151,936,
PTC blocks of k=128).

    python chip_smoke.py [--seed N]

Run it from the checkout root on a machine with one TPU.  Phases:

* serve — ``repro.serving.gateway.run`` on qwen3-4b cut to 8 layers,
  8 slots of 64 pages × 16 tokens, 8 open-loop requests (prompts of
  128–512 tokens, 16–32 new tokens), once at prefill chunk 1 and once
  at 32, with the embedding table scaled down so that the context, not
  the last token, decides the output.  Every request must finish with
  its token count, every served token must agree with
  ``models.lm.forward`` over the prompt and the tokens served before
  it, and at least two first tokens must differ from what the last
  prompt token alone would give.  Before that, the paged KV gather and
  scatters at the gateway's pool shape must match jnp indexing bitwise.
* train — 3 Σ-only update steps (``launch.steps.build_update_step``,
  params and optimiser state donated) at 2 layers, batch 4 × 512, with
  the paper's feedback and column sparsity (α_W = α_C = 0.6).  The loss
  must stay finite, Σ must move and every U/V leaf must stay bitwise.
* twin — the closed-loop runtime demo at its fast-smoke size, and one
  ``TwinDriver`` layer forward and one probe forward over the q
  projection's 32×20 grid of k=128 blocks through the Pallas kernel,
  each against the einsum route.

Weights, requests and batches come from ``--seed``.  The times printed
are smoke timings of one cold run, compiles included, not benchmark
results.  A failing phase prints its traceback and the script exits 1
after the others have run; with no TPU it exits 2 before any phase.
Only when every check passed is the last line the JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SERVE_LAYERS = 8        # 36 → 8: params + KV pool fit one 16 GB chip
TRAIN_LAYERS = 2
SLOTS, PAGE_SIZE, PAGES_PER_SLOT = 8, 16, 64
PROMPT_LEN, MAX_NEW = (128, 512), (16, 32)
TWIN_TOKENS = 37        # not a multiple of 8: the kernel's whole-T tile
EMBED_SCALE = 1 / 16    # served weights: see serve_phase


class CompileClock:
    """Sums XLA backend-compile durations reported by JAX."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            self.seconds += duration


def _log(msg: str) -> None:
    print(msg, flush=True)


def kv_kernels_check(seed: int, cfg) -> None:
    """The paged KV kernels as the gateway calls them, at its pools' shape
    (per layer, SLOTS·PAGES_PER_SLOT pages and a scratch page), against
    jnp indexing: the gather of every slot's page table, the one-row
    scatter (one row per slot and layer, as at C=1) and the multi-row
    scatter (32 per slot and layer, as at C=32).  They only move bytes,
    so they must match bitwise; a row written to a wrong page or offset,
    or a wrong row written, shows here even where the served tokens
    cannot see it (attention over random weights spreads over hundreds
    of positions)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    layers = cfg.n_layers
    shape = (layers * (SLOTS * PAGES_PER_SLOT + 1), PAGE_SIZE,
             cfg.n_kv_heads, cfg.head_dim)
    kp, kt, ki, kr = jax.random.split(jax.random.PRNGKey(seed), 4)
    pool = jax.random.normal(kp, shape, jnp.bfloat16)
    table = jax.random.randint(kt, (layers * SLOTS, PAGES_PER_SLOT), 0,
                               shape[0], jnp.int32)
    got = ops.paged_gather(table, pool)
    checks = {f"gather of {table.shape[0]}x{table.shape[1]} pages":
              (got, pool[table].reshape(got.shape))}
    for name, fn, per_slot in (("paged_scatter", ops.paged_scatter, 1),
                               ("paged_scatter_rows",
                                ops.paged_scatter_rows, 32)):
        n = layers * SLOTS * per_slot
        # distinct targets: jnp's scatter leaves duplicates unordered
        flat = jax.random.choice(ki, shape[0] * PAGE_SIZE, (n,),
                                 replace=False)
        idx = jnp.stack([flat // PAGE_SIZE, flat % PAGE_SIZE],
                        1).astype(jnp.int32)
        rows = jax.random.normal(kr, (n,) + shape[2:], jnp.bfloat16)
        checks[f"{name} of {n} rows"] = (
            fn(idx, rows, pool), pool.at[idx[:, 0], idx[:, 1]].set(rows))
    for name, (got, want) in checks.items():
        if got.shape != want.shape:
            raise AssertionError(f"{name}: shape {got.shape}, jnp indexing "
                                 f"gives {want.shape}")
        wrong = int(jnp.any(got != want, axis=(-2, -1)).sum())
        _log(f"serve: {name} in a {shape} bf16 pool vs jnp indexing: "
             f"{'bitwise equal' if wrong == 0 else f'{wrong} rows differ'}")
        if wrong:
            raise AssertionError(f"{name}: the kernel disagrees with jnp "
                                 f"indexing ({wrong} rows differ)")


def serve_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.models.lm import forward, init_model
    from repro.serving.gateway import run
    from repro.serving.scheduler import poisson_workload

    full = get_config("qwen3-4b")
    cfg = dataclasses.replace(full, n_layers=SERVE_LAYERS)
    kv_kernels_check(seed, cfg)
    params = jax.jit(init_model, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)
    # At random init the embedding, scaled by sqrt(d_model), dominates the
    # residual stream, and with tied embeddings every argmax is then the
    # last token fed in, whatever the context: a serving path that lost
    # or misplaced its KV cache would still match.  A smaller table lets
    # the attention over the context decide the tokens.
    params = {**params, "embed": {"e": params["embed"]["e"] * EMBED_SCALE}}
    n_bytes = sum(a.nbytes for a in jax.tree.leaves(params))
    _log(f"serve: qwen3-4b, every width kept, depth cut {full.n_layers} -> "
         f"{cfg.n_layers} layers, embedding table scaled by {EMBED_SCALE}; "
         f"parameter bytes {n_bytes}")
    reqs = poisson_workload(seed, SLOTS, 0.5, cfg.vocab,
                            prompt_len=PROMPT_LEN, max_new=MAX_NEW)
    n = len(reqs)
    plen = np.asarray([r.prompt_len for r in reqs])

    # reference: the plain full-sequence forward, right-padded into one
    # batch (causal, so padding never reaches a checked position), at
    # the positions that predict each generated token
    @jax.jit
    def reference(p, toks, pos):
        logits, _ = forward(p, cfg, {"tokens": toks})
        return logits[jnp.arange(toks.shape[0])[:, None],
                      pos].astype(jnp.float32)

    # the same forward over the last prompt token alone: what the first
    # token would be if the context were lost
    alone = np.asarray(reference(
        params, jnp.asarray([r.prompt[-1:] for r in reqs]),
        jnp.zeros((n, 1), jnp.int32)))[:, 0].argmax(-1)

    # Tolerance.  Both paths compute in bf16 but attend through different
    # code (paged bf16 KV pool + Pallas online softmax vs a dense softmax)
    # and round at different points.  δ = σ/16, σ the spread of the
    # position's reference logits, is taken to bound that error in each
    # logit; the served token's reference logit must then lie within
    # tol = 2δ of the reference maximum (its "deficit"), and where the
    # reference's top-2 gap exceeds tol the argmax must match.  The
    # largest deficit seen is printed, in units of σ, as the measured
    # error: on a TPU v5e at seed 0 it was 0 at C=1 and 0.031σ at C=32,
    # on one near-tie of 197 tokens, a quarter of tol.
    served = {}
    for chunk in (1, 32):
        args = argparse.Namespace(
            arch=cfg, seed=seed, slots=SLOTS, requests=n, rate=0.5,
            page_size=PAGE_SIZE, pages=SLOTS * PAGES_PER_SLOT,
            max_pages_per_slot=PAGES_PER_SLOT, max_new=MAX_NEW,
            prompt_len_range=PROMPT_LEN, eos_id=None, prefill_chunk=chunk,
            params_override=params,
            requests_override=[dataclasses.replace(r, out_tokens=[])
                               for r in reqs])
        t0 = time.perf_counter()
        rep = run(args)
        wall = time.perf_counter() - t0
        done = rep["requests"]
        counts_ok = (len(done) == n and all(
            d["n_out"] == d["max_new"] and d["finish_reason"] == "max_new"
            for d in done))
        _log(f"serve C={chunk}: {len(done)}/{n} requests finished, "
             f"{rep['tokens_out']} tokens over {rep['steps']} steps, "
             f"token counts {'ok' if counts_ok else 'WRONG'}; "
             f"smoke wall {wall:.1f} s (cold, compiles included)")
        if not counts_ok:
            raise AssertionError(f"C={chunk}: a request did not finish with "
                                 f"its token count: {done}")
        # teacher-forced: the reference reads the prompt and the served
        # tokens, and scores every served token given what came before
        out = np.zeros((n, MAX_NEW[1]), np.int32)
        valid = np.zeros((n, MAX_NEW[1]), bool)
        toks = np.zeros((n, PROMPT_LEN[1] + MAX_NEW[1]), np.int32)
        for i, (r, d) in enumerate(zip(reqs, done)):
            out[i, :d["n_out"]] = d["tokens"]
            valid[i, :d["n_out"]] = True
            toks[i, :r.prompt_len] = r.prompt
            toks[i, r.prompt_len:r.prompt_len + d["n_out"] - 1] = \
                d["tokens"][:-1]
        pos = plen[:, None] - 1 + np.arange(MAX_NEW[1])[None]
        ref = np.asarray(reference(params, jnp.asarray(toks),
                                   jnp.asarray(pos, jnp.int32)))
        tol = ref.std(-1) / 8.0
        top2 = -np.sort(-ref, axis=-1)[..., :2]
        gap = top2[..., 0] - top2[..., 1]
        deficit = ref.max(-1) - np.take_along_axis(
            ref, out[..., None], -1)[..., 0]
        decisive = (gap > tol) & valid
        match = out == ref.argmax(-1)
        bad = valid & ((deficit > tol) | (decisive & ~match))
        # the first token: does the context decide it?
        ctx = ref[:, 0].argmax(-1) != alone
        for i, d in enumerate(done):
            _log(f"  request {d['rid']}: prompt {d['prompt_len']}, first "
                 f"token {out[i, 0]} vs reference argmax "
                 f"{int(ref[i, 0].argmax())} (last prompt token alone: "
                 f"{int(alone[i])}); top-2 gap {gap[i, 0]:.4f}, deficit "
                 f"{deficit[i, 0]:.4f}, tol {tol[i, 0]:.4f} "
                 f"({'decisive' if decisive[i, 0] else 'tie'})")
        rel = deficit[valid] / (8.0 * tol[valid])
        _log(f"serve C={chunk}: {int(valid.sum())} served tokens scored, "
             f"{int(decisive.sum())} decisive, {int((decisive & match).sum())}"
             f" decisive argmax matches, {int(bad.sum())} beyond tolerance; "
             f"deficit/sigma max {rel.max():.4f}, mean {rel.mean():.4f}, "
             f"nonzero {int((rel > 0).sum())}; first token decided by the "
             f"context (decisive, matched, unlike the last token alone) "
             f"for {int((ctx & decisive[:, 0] & match[:, 0]).sum())}/{n}")
        if bad.any():
            raise AssertionError(f"C={chunk}: served tokens disagree with "
                                 f"the reference beyond tolerance")
        if (ctx & decisive[:, 0] & match[:, 0]).sum() < 2:
            raise AssertionError(f"C={chunk}: fewer than two first tokens "
                                 f"are decided by the context")
        served[chunk] = [d["tokens"] for d in done]
    same = sum(a == b for a, b in zip(served[1], served[32]))
    _log(f"serve: C=1 and C=32 emitted identical sequences for {same}/"
         f"{n} requests (bf16: not required)")


def train_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core.sparsity import SparsityConfig
    from repro.data import lm_batch
    from repro.launch.steps import build_update_step, init_train_state
    from repro.optim.optimizers import AdamWConfig

    batch, seq, n_steps = 4, 512, 3
    cfg = dataclasses.replace(get_config("qwen3-4b"), n_layers=TRAIN_LAYERS)
    params, opt = jax.jit(init_train_state, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)

    def leaves(tree, names):
        return [(jax.tree_util.keystr(path), leaf) for path, leaf in
                jax.tree_util.tree_leaves_with_path(tree)
                if getattr(path[-1], "key", None) in names]

    # donation invalidates the inputs: keep device copies to compare with
    uv0 = [(n, jnp.copy(a)) for n, a in leaves(params, ("u", "v"))]
    s0 = [(n, jnp.copy(a)) for n, a in leaves(params, ("s",))]
    update = jax.jit(build_update_step(
        cfg, AdamWConfig(), SparsityConfig(alpha_w=0.6, alpha_c=0.6)),
        donate_argnums=(0, 1))
    _log(f"train: qwen3-4b, every width kept, depth cut -> {cfg.n_layers} "
         f"layers; batch {batch} x {seq}, alpha_W = alpha_C = 0.6, "
         f"{len(s0)} Sigma leaves, {len(uv0)} U/V leaves")
    losses = []
    t0 = time.perf_counter()
    for step in range(n_steps):
        b = {k: jnp.asarray(v) for k, v in
             lm_batch(seed, step, batch, seq, cfg.vocab).items()}
        params, opt, loss, gnorm = update(params, opt, b,
                                          jax.random.fold_in(
                                              jax.random.PRNGKey(seed), step))
        losses.append(float(loss))
        _log(f"  step {step}: loss {losses[-1]:.4f} gnorm {float(gnorm):.4f}")
    wall = time.perf_counter() - t0
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    moved = sum(not bool(jnp.array_equal(a, b)) for (_, a), (_, b) in
                zip(s0, leaves(params, ("s",))))
    frozen = [n for (n, a), (_, b) in zip(uv0, leaves(params, ("u", "v")))
              if not bool(jnp.array_equal(a, b))]
    _log(f"train: {moved}/{len(s0)} Sigma leaves moved, "
         f"{len(uv0) - len(frozen)}/{len(uv0)} U/V leaves bitwise "
         f"unchanged; smoke wall {wall:.1f} s for {n_steps} steps "
         f"(cold, compile included)")
    if moved != len(s0):
        raise AssertionError("a Sigma leaf did not move")
    if frozen:
        raise AssertionError(f"frozen bases changed: {frozen}")


def twin_phase(seed: int) -> None:
    import jax
    import numpy as np

    from repro.core.noise import DEFAULT_NOISE
    from repro.hw import make_twin
    from repro.runtime import demo

    t0 = time.perf_counter()
    rc = demo.main(["--chips", "2", "--steps", "40", "--dim", "12",
                    "--k", "4", "--probe-every", "5", "--sigma-drift",
                    "0.04", "--seed", str(seed)])
    _log(f"twin: runtime demo (fast-smoke flags) returned {rc}; smoke wall "
         f"{time.perf_counter() - t0:.1f} s")
    if rc != 0:
        raise AssertionError(f"runtime demo returned {rc}")

    # qwen3-4b's q projection: 4096 x 2560 = a 32 x 20 grid of k=128
    # blocks, driven as a layer (T x 2560 in) and as per-block probes
    # (T x 128 in, one output per block), each through both routes
    k, p, q = 128, 32, 20
    key = jax.random.PRNGKey(seed)
    model = DEFAULT_NOISE.post_ic()
    rng = np.random.default_rng(seed)
    sigma = rng.uniform(0.5, 1.5, (p * q, k)).astype(np.float32)
    probes = {"layer forward": (rng.standard_normal(
                  (TWIN_TOKENS, q * k)).astype(np.float32),
                  (TWIN_TOKENS, p * k)),
              "probe forward": (rng.standard_normal(
                  (TWIN_TOKENS, k)).astype(np.float32),
                  (p * q, TWIN_TOKENS, k))}
    ys = {}
    # both routes in f32 at full matmul precision, so they differ only by
    # reassociation; a misplaced block or Σ moves outputs by O(their scale)
    with jax.default_matmul_precision("highest"):
        for route in (True, False):
            drv = make_twin(key, p * q, k, model, m=p * k, n=q * k,
                            use_kernels=route)
            drv.write_sigma(sigma)
            ys[route] = {"layer forward": drv.forward_layer(
                             probes["layer forward"][0]),
                         "probe forward": drv.forward(
                             probes["probe forward"][0])}
    tol = 1e-3
    for name, (_, shape) in probes.items():
        got, want = (np.asarray(ys[r][name]) for r in (True, False))
        scale = float(np.abs(want).max())
        err = float(np.abs(got - want).max()) / scale
        _log(f"twin: TwinDriver {name}, {p}x{q} blocks of k={k}, "
             f"{TWIN_TOKENS} tokens: Pallas kernel vs einsum max error "
             f"{err:.3e} of the output scale {scale:.3f} (tol {tol:g})")
        if got.shape != shape or not err <= tol:
            raise AssertionError(f"{name}: kernel route disagrees with "
                                 f"einsum: shape {got.shape}, error {err}")


PHASES = (("serve", serve_phase), ("train", train_phase),
          ("twin", twin_phase))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds weights, requests, batches and twins")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              f"no phase was run", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache

    _log(f"device: {dev.device_kind} ({dev.platform}), {len(devices)} "
         f"chip(s); compile cache {enable_compile_cache()}")
    clock = CompileClock()
    failed = []
    for name, phase in PHASES:
        t0, c0 = time.perf_counter(), clock.seconds
        try:
            phase(args.seed)
            status = "passed"
        except Exception:
            traceback.print_exc()
            failed.append(name)
            status = "FAILED"
        stats = dev.memory_stats() or {}
        _log(f"phase {name}: {status}; smoke wall "
             f"{time.perf_counter() - t0:.1f} s, of it XLA compile "
             f"{clock.seconds - c0:.1f} s; peak device memory so far "
             f"{stats.get('peak_bytes_in_use', 'not reported')} bytes")
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
