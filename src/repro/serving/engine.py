"""Continuous-batching gateway engine: lockstep decode over slot batches.

One virtual step = one batched single-token forward over every active
slot (``models.lm.build_gateway_step``): page-assembled KV views in,
logits + new KV rows out, rows scattered back into the page pool
(``kernels.paged_gather`` / ``paged_scatter``).  Admission, eviction
and paging policy live in ``scheduler``/``kv_pages``; hardware-in-the-
loop execution rides the existing :class:`~repro.runtime.hw_serve.
HwServePlane` — the gateway installs the plane's PTC hook around its
loop, so each layer's matmul for ALL in-flight requests ships as one
coalesced driver frame to the routed chip.

Digital mode jits the step (static shapes: slot count, view lengths and
pool geometry never change — only table/length *contents* do).
Hardware mode runs it unjitted over an ``unroll=True`` config, exactly
like ``serve --hw-logits`` (the hook needs concrete activations).
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.ops import paged_gather, paged_scatter, paged_scatter_rows
from ..models.lm import (ArchConfig, build_gateway_prefill_step,
                         build_gateway_step, build_serve_step,
                         init_decode_cache, period_plan)
from ..models.ssm import init_ssm_state
from .kv_pages import PageConfig, PagedKVPool
from .scheduler import (Request, Scheduler, FINISH_EOS, FINISH_MAX_NEW)

__all__ = ["GatewayConfig", "ServingGateway", "build_gateway_hw_plane"]


@dataclasses.dataclass(frozen=True)
class GatewayConfig:
    """Static gateway geometry/policy."""

    slots: int = 4               # concurrent decode streams
    pages: PageConfig = PageConfig()
    max_steps: int = 100_000     # hard stop for the run loop
    # chunked prefill: each prefilling slot ingests up to prefill_chunk
    # prompt tokens per virtual step through the (B, C)-wide prefill
    # step while decode slots ride along producing one token each.
    # 1 = the original one-token-per-step path, bit-for-bit.
    prefill_chunk: int = 1
    # test/debug knob: cap tokens *advanced* per step below the padded
    # width C.  stride s at width C is bitwise-identical in KV and
    # tokens to stride C at width C (row-position invariance at fixed
    # shape) — the property tests' comparison lever.  None = C.
    prefill_stride: int | None = None


def build_gateway_hw_plane(key, cfg: ArchConfig, params, runtime_cfg,
                           n_chips: int, *, slots: int, mode: str = "route",
                           seed: int = 0, recal_enabled: bool = True):
    """Deploy the model's decode-path PTC layers onto a fresh fleet for
    gateway serving (one tenant per layer, exactly the ``serve
    --hw-logits`` deployment).  ``cfg`` must be the unrolled config the
    gateway step will run; layer enumeration uses the *solo* serve step,
    whose scope names the gateway step reproduces."""
    from ..runtime.hw_serve import HwServePlane, record_ptc_layers

    serve_fn = build_serve_step(cfg)
    cache0 = init_decode_cache(cfg, slots, 2)
    batch0 = {"token": jnp.zeros((slots, 1), jnp.int32),
              "cache_len": jnp.asarray(0, jnp.int32)}
    layers = record_ptc_layers(serve_fn, params, cache0, batch0)
    return HwServePlane(key, layers, runtime_cfg, n_chips, mode=mode,
                        seed=seed, recal_enabled=recal_enabled)


class ServingGateway:
    """The request-level serving loop over one model + optional fleet."""

    def __init__(self, cfg: ArchConfig, params, gcfg: GatewayConfig,
                 hw_plane=None):
        if hw_plane is not None and not cfg.unroll:
            raise ValueError("hardware-in-the-loop gateway needs an "
                             "unroll=True config (the PTC hook is inert "
                             "under jit/scan)")
        self.cfg = cfg
        self.gcfg = gcfg
        self.params = params
        self.hw = hw_plane
        self.plan, self.n_periods = period_plan(cfg)
        self.pool = PagedKVPool(gcfg.pages, gcfg.slots)
        self.chunk = max(1, int(gcfg.prefill_chunk))
        self.stride = (self.chunk if gcfg.prefill_stride is None
                       else max(1, min(int(gcfg.prefill_stride), self.chunk)))
        if self.chunk > 1:
            self._step_fn = build_gateway_prefill_step(cfg)
        else:
            self._step_fn = build_gateway_step(cfg)
        if hw_plane is None:
            self._step_fn = jax.jit(self._step_fn)

        # tensor pools: one (P·(n_pages+1), page_size, Hkv, Dh) pair per
        # attention sub-layer position — all periods share the slot page
        # table (token t lives at the same page/offset in every layer),
        # each period's pages offset by its stripe.  The +1 page per
        # stripe is the scratch page idle slots scatter into.
        ps = gcfg.pages.page_size
        self._stripe = gcfg.pages.n_pages + 1
        self._scratch = gcfg.pages.n_pages      # id of the scratch page
        self._kv_dims: dict[str, tuple[int, int]] = {}
        self._pools: dict[str, dict[str, jax.Array]] = {}
        self._ssm0: dict[str, dict] = {}
        self._ssm: dict[str, dict] = {}
        kv_dtype = jnp.bfloat16
        for i, sub in enumerate(self.plan):
            name = f"pos{i}"
            if sub.kind == "attn":
                acfg = cfg.attn_cfg(sub.window)
                hk, hd = acfg.n_kv_heads, acfg.head_dim
                self._kv_dims[name] = (hk, hd)
                shape = (self.n_periods * self._stripe, ps, hk, hd)
                self._pools[name] = {"k": jnp.zeros(shape, kv_dtype),
                                     "v": jnp.zeros(shape, kv_dtype)}
            else:
                one = init_ssm_state(gcfg.slots, cfg.ssm_cfg())
                stacked = jax.tree.map(
                    lambda a: jnp.broadcast_to(
                        a[None], (self.n_periods,) + a.shape), one)
                self._ssm0[name] = stacked
                self._ssm[name] = stacked

        # counters
        self.step_count = 0
        self.busy_steps = 0
        self.slot_steps = 0          # Σ active slots over busy steps
        # Σ over busy steps of the KV positions each active slot's
        # attention covers (its length before the step plus the tokens
        # it takes in), against busy_steps · slots · S_max gathered
        self.kv_live_positions = 0
        self.tokens_out = 0

    # -- paged-pool plumbing -------------------------------------------------

    def _period_table(self) -> np.ndarray:
        """(P·B, J) page table with per-period stripe offsets."""
        t = self.pool.table
        return np.concatenate(
            [t + p * self._stripe for p in range(self.n_periods)], axis=0)

    def _gather_views(self) -> dict:
        """Assemble every attention position's (P, B, S_max, Hkv, Dh)
        views from the pools, each in the gather's own program (no
        reshape after it, which would copy the view); SSM positions pass
        their dense states."""
        lead = (self.n_periods, self.gcfg.slots)
        table = jnp.asarray(self._period_table())
        views = {}
        for name, pools in self._pools.items():
            views[name] = {kk: paged_gather(table, pools[kk], lead=lead)
                           for kk in ("k", "v")}
        for name, st in self._ssm.items():
            views[name] = st
        return views

    def _scatter_new(self, new_kv: dict, active: Sequence[int]) -> None:
        """Persist each active slot's new KV row at its write position;
        idle slots land on the scratch page.  The scatter consumes the
        pool it is given, in place, and the result replaces it.  SSM
        replacement states are adopted wholesale (idle slots' states are
        reset on admit)."""
        b = self.gcfg.slots
        idx = np.zeros((b, 2), np.int32)
        idx[:, 0] = self._scratch
        for slot in active:
            pid, off = self.pool.write_pos(slot)
            idx[slot] = (pid, off)
        full_idx = np.concatenate(
            [idx + np.asarray([[p * self._stripe, 0]], np.int32)
             for p in range(self.n_periods)], axis=0)
        full_idx = jnp.asarray(full_idx)
        for name, pools in self._pools.items():
            hk, hd = self._kv_dims[name]
            rows = new_kv[name]     # {"k","v"}: (P, B, 1, Hkv, Dh)
            for kk in ("k", "v"):
                flat = rows[kk].reshape(self.n_periods * b, hk, hd)
                pools[kk] = paged_scatter(
                    full_idx, flat.astype(pools[kk].dtype), pools[kk])
        for name in self._ssm:
            self._ssm[name] = new_kv[name]

    def _scatter_chunk(self, new_kv: dict, act: np.ndarray,
                       take: np.ndarray) -> None:
        """Persist each active slot's first ``take[slot]`` new KV rows
        at its consecutive write positions — chunks crossing page
        boundaries are split host-side by ``PagedKVPool.write_span`` —
        through ONE aliased multi-row scatter per pool tensor.  Padding
        columns and idle slots land on the scratch page (the scatter
        grid is sequential, so the duplicate scratch writes resolve
        deterministically)."""
        b, c = self.gcfg.slots, self.chunk
        idx = np.zeros((b, c, 2), np.int32)
        idx[:, :, 0] = self._scratch
        for slot in np.flatnonzero(act):
            n = int(take[slot])
            if n:
                idx[slot, :n] = self.pool.write_span(slot, n)
        full_idx = np.concatenate(
            [idx.reshape(b * c, 2)
             + np.asarray([[p * self._stripe, 0]], np.int32)
             for p in range(self.n_periods)], axis=0)
        full_idx = jnp.asarray(full_idx)
        for name, pools in self._pools.items():
            hk, hd = self._kv_dims[name]
            rows = new_kv[name]     # {"k","v"}: (P, B, C, Hkv, Dh)
            for kk in ("k", "v"):
                flat = rows[kk].reshape(self.n_periods * b * c, hk, hd)
                pools[kk] = paged_scatter_rows(
                    full_idx, flat.astype(pools[kk].dtype), pools[kk])

    def _reset_slot(self, slot: int) -> None:
        """Zero an admitted slot's SSM state (pages need no reset: the
        slot writes before it reads, and attention masks by length)."""
        for name, st in self._ssm.items():
            self._ssm[name] = jax.tree.map(
                lambda a: a.at[:, slot].set(0), st)

    # -- the loop ------------------------------------------------------------

    def run(self, requests: Sequence[Request]) -> dict:
        """Serve ``requests`` (arrival steps respected — the open-loop
        process) to completion; returns the report dict.

        Token staging is vectorized: per-slot prompt buffers, lengths
        and cursors live in NumPy arrays refreshed at admission /
        emission, so each step's (B, C) token block is pure fancy
        indexing — no per-slot scalar writes on the hot path.  With
        ``prefill_chunk`` C > 1 a prefilling slot ingests up to
        min(prefill_stride, remaining) prompt tokens per step while
        decode slots produce one token each (n_valid == 1), all through
        one (B, C)-wide forward.

        Each busy step runs inside a ``jax.profiler`` step span
        ``gateway.step`` holding one span per phase, in order:
        ``gateway.admit``, ``stage``, ``gather``, ``forward``,
        ``scatter``, ``fetch`` (the blocking device-to-host argmax) and
        ``emit``.  They cost well under a microsecond each while no
        profile is recorded."""
        sched = Scheduler(self.pool)
        todo = sorted(requests, key=lambda r: (r.arrival, r.rid))
        next_arrival = 0
        from ..models.layers import ptc_execution
        hook_ctx = (ptc_execution(self.hw.hook) if self.hw is not None
                    else contextlib.nullcontext())
        b, chunk, stride = self.gcfg.slots, self.chunk, self.stride
        buf_len = self.gcfg.pages.max_tokens_per_slot
        prompt_buf = np.zeros((b, buf_len), np.int32)
        plen = np.zeros((b,), np.int32)      # prompt length per slot
        slot_pos = np.zeros((b,), np.int32)  # decode position per slot
        last_tok = np.zeros((b,), np.int32)  # last emitted token per slot
        arange_b = np.arange(b)
        arange_c = np.arange(chunk)
        t0 = time.perf_counter()
        span = jax.profiler.TraceAnnotation
        with hook_ctx:
            while self.step_count < self.gcfg.max_steps:
                step = self.step_count
                # no slot running and no arrival due: an idle step
                if sched.idle and not (next_arrival < len(todo)
                                       and todo[next_arrival].arrival <= step):
                    if next_arrival >= len(todo):
                        break                      # drained
                    # open-loop gap: virtual time still passes (drift
                    # walks, probes/repairs run) while no one is here —
                    # and the autopilot sees the trough (zero occupancy)
                    if self.hw is not None:
                        self.hw.observe_load(0.0)
                        self.hw.router.tick()
                    self.step_count += 1
                    continue

                with jax.profiler.StepTraceAnnotation("gateway.step",
                                                      step_num=step):
                    with span("gateway.admit"):
                        while (next_arrival < len(todo)
                               and todo[next_arrival].arrival <= step):
                            sched.submit(todo[next_arrival], step)
                            next_arrival += 1
                        for slot, req in sched.admit(step):
                            slot_pos[slot] = 0
                            plen[slot] = req.prompt_len
                            prompt_buf[slot, :req.prompt_len] = req.prompt
                            self._reset_slot(slot)

                    with span("gateway.stage"):
                        act = np.asarray([r is not None
                                          for r in sched.running])
                        if self.hw is not None:
                            # occupancy signal for the autopilot's load
                            # forecast: active slots plus queued requests,
                            # over capacity (>1 = over-subscribed)
                            self.hw.observe_load(
                                (int(act.sum()) + len(sched.pending)) / b)
                        pre = act & (slot_pos < plen)
                        dec = act & ~pre
                        # tokens each slot ingests this step (idle: none)
                        take = np.where(
                            pre, np.minimum(stride, plen - slot_pos),
                            act.astype(np.int32))
                        cols = slot_pos[:, None] + arange_c[None, :]  # (B, C)
                        valid = arange_c[None, :] < take[:, None]
                        tok = np.where(
                            pre[:, None] & valid,
                            prompt_buf[arange_b[:, None],
                                       np.minimum(cols, buf_len - 1)],
                            0).astype(np.int32)
                        tok[dec, 0] = last_tok[dec]
                        batch = {"token": jnp.asarray(tok),
                                 "lens": jnp.asarray(self.pool.lens)}
                        if chunk > 1:
                            batch["n_valid"] = jnp.asarray(
                                np.maximum(take, 1).astype(np.int32))

                    with span("gateway.gather"):
                        views = self._gather_views()

                    with span("gateway.forward"):
                        step_ctx = (self.hw.step(
                            step, valid=valid if chunk > 1 else None)
                            if self.hw is not None
                            else contextlib.nullcontext())
                        with step_ctx:
                            logits, new_kv = self._step_fn(self.params,
                                                           views, batch)
                        # the next gather may reuse these views' memory
                        del views

                    with span("gateway.scatter"):
                        if chunk > 1:
                            self._scatter_chunk(new_kv, act, take)
                        else:
                            self._scatter_new(new_kv,
                                              list(np.flatnonzero(act)))

                    with span("gateway.fetch"):
                        preds = np.asarray(jnp.argmax(logits, axis=-1))

                    with span("gateway.emit"):
                        # positions each active slot attended over: its
                        # cache before the step and what it took in
                        self.kv_live_positions += int(
                            (self.pool.lens + take)[act].sum())
                        for slot in np.flatnonzero(act):
                            req = sched.running[slot]
                            n = int(take[slot])
                            self.pool.advance(slot, n)
                            pos = slot_pos[slot] = slot_pos[slot] + n
                            if pos < plen[slot]:
                                continue                 # still prefilling
                            nxt = int(preds[slot])
                            req.out_tokens.append(nxt)
                            last_tok[slot] = nxt
                            self.tokens_out += 1
                            if req.first_token_step < 0:
                                req.first_token_step = step
                            if req.eos_id is not None and nxt == req.eos_id:
                                sched.finish(slot, step, FINISH_EOS)
                            elif len(req.out_tokens) >= req.max_new:
                                sched.finish(slot, step, FINISH_MAX_NEW)
                        self.busy_steps += 1
                        self.slot_steps += int(act.sum())
                        self.step_count += 1
        wall = time.perf_counter() - t0
        if not sched.idle:
            raise RuntimeError(
                f"gateway hit max_steps={self.gcfg.max_steps} with "
                f"{len(sched.pending)} queued / {sched.n_active} running "
                f"requests unfinished")
        return self._report(sched, wall)

    # -- reporting -----------------------------------------------------------

    def _report(self, sched: Scheduler, wall: float) -> dict:
        reqs = sorted(sched.finished, key=lambda r: r.rid)
        lats = np.asarray([r.latency() for r in reqs], np.float64)
        waits = np.asarray([r.admitted_step - r.arrival for r in reqs],
                           np.float64)
        ttfts = np.asarray([r.ttft() for r in reqs], np.float64)
        rep = dict(
            requests=[dict(rid=r.rid, prompt_len=r.prompt_len,
                           max_new=r.max_new, arrival=r.arrival,
                           admitted=r.admitted_step,
                           first_token=r.first_token_step,
                           finished=r.finished_step,
                           finish_reason=r.finish_reason,
                           n_out=len(r.out_tokens),
                           tokens=list(map(int, r.out_tokens)))
                      for r in reqs],
            steps=self.step_count, busy_steps=self.busy_steps,
            occupancy=(self.slot_steps / self.busy_steps
                       if self.busy_steps else 0.0),
            tokens_out=self.tokens_out, wall_s=wall,
            tokens_per_s=self.tokens_out / wall if wall > 0 else 0.0,
            latency_steps=dict(
                p50=float(np.percentile(lats, 50)) if len(lats) else 0.0,
                p99=float(np.percentile(lats, 99)) if len(lats) else 0.0,
                mean=float(lats.mean()) if len(lats) else 0.0),
            ttft_steps=dict(
                p50=float(np.percentile(ttfts, 50)) if len(ttfts) else 0.0,
                p99=float(np.percentile(ttfts, 99)) if len(ttfts) else 0.0,
                mean=float(ttfts.mean()) if len(ttfts) else 0.0),
            admission_wait_steps=dict(
                p50=float(np.percentile(waits, 50)) if len(waits) else 0.0,
                p99=float(np.percentile(waits, 99)) if len(waits) else 0.0),
            schedule_trace=list(sched.trace),
        )
        if self.hw is not None:
            rep["fleet"] = self.hw.report()
        return rep

    def close(self) -> None:
        if self.hw is not None:
            self.hw.close()
