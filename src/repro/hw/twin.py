"""`TwinDriver`: the in-process digital-twin implementation of the ABC.

Wraps a :class:`DeviceRealization` + :class:`DriftState` behind the
:class:`~repro.hw.driver.PhotonicDriver` surface.  All ops evaluate the
same pure twin physics (``repro.hw.device``) the simulator has always
used, so the driver boundary costs nothing numerically; the in-situ
jobs delegate to ``repro.hw.jobs`` (vmapped ``lax.scan`` searches — the
jit-friendly path).

Drift entropy is device-owned: the driver holds its own PRNG chain
(seeded at construction), so a fleet trajectory is reproducible from
construction seeds alone and the control plane never supplies drift
randomness — mirroring real hardware, which drifts without being asked.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core import unitary as un
from ..core.noise import NoiseModel
from ..kernels.ptc_block_matmul import accepts as kernel_accepts
from ..optim.zo import ZOConfig
from . import jobs
from .device import (DeviceRealization, sample_device, realized_unitaries,
                     realized_blocks, true_mapping_distance, chip_forward)
from .drift import DriftConfig, DriftState, init_drift, advance, \
    bias_deviation
from .driver import (PhotonicDriver, DriverStats, ZORefineResult, ICJobResult,
                     probe_cost, readback_cost, resolve_block_range,
                     forward_coalesce_key, coalesce_spans,
                     validate_batch_ops)

__all__ = ["TwinDriver", "TwinHandle", "make_twin"]


class TwinHandle:
    """Quarantined readouts of a twin's internals (tests/benchmarks only).

    Obtained exclusively through ``driver.unsafe_twin()`` — the single
    audited hole in the observability boundary.
    """

    def __init__(self, driver: "TwinDriver"):
        self._d = driver

    @property
    def dev(self) -> DeviceRealization:
        """The current (drifted) device realization."""
        return self._d._state.dev

    @property
    def anchor(self) -> DeviceRealization:
        """The manufacturing realization the OU drift reverts to."""
        return self._d._state.anchor

    @property
    def drift_state(self) -> DriftState:
        return self._d._state

    def realized_unitaries(self) -> tuple[jax.Array, jax.Array]:
        """Free full readout of the realized bases (no PTC charge)."""
        d = self._d
        t = d._spec.n_rot
        return realized_unitaries(d._spec, d._phi[:, :t], d._phi[:, t:],
                                  d._state.dev, d._model)

    def realized_blocks(self) -> jax.Array:
        d = self._d
        return realized_blocks(d._spec, d._phi, d._sigma, d._state.dev,
                               d._model)

    def true_mapping_distance(self, w_blocks: jax.Array,
                              block_range: tuple[int, int] | None = None
                              ) -> float:
        """Exact aggregate mapping distance (full-readout ground truth).
        ``block_range`` scopes it to one tenant's blocks (``w_blocks``
        then carries the range's block count)."""
        d = self._d
        start, stop = resolve_block_range(d._b, block_range)
        dev = jax.tree_util.tree_map(lambda a: a[start:stop], d._state.dev)
        return float(true_mapping_distance(
            d._spec, d._phi[start:stop], d._sigma[start:stop], dev,
            d._model, w_blocks))

    def bias_deviation(self) -> float:
        """RMS phase-bias deviation from the anchor (radians)."""
        return float(bias_deviation(self._d._state))


def _scope(phi, sigma, dev, start: int, stop: int):
    """Tenant-scope the commanded state + device INSIDE the compiled
    graph: ``start``/``stop`` are static, so each (shape, block_range)
    signature compiles once and the per-call python cost is a pure
    cache-hit dispatch — the twin fast path the stream servers also ride."""
    dev = jax.tree_util.tree_map(lambda a: a[start:stop], dev)
    return phi[start:stop], sigma[start:stop], dev


@functools.lru_cache(maxsize=64)
def _jitted_probe_ops(k: int, kind: str, model: NoiseModel,
                      use_kernels: bool):
    """Compiled forward/readback graphs keyed on the driver's static
    physics (NoiseModel is a frozen dataclass, hence hashable).

    With ``use_kernels`` (see :class:`TwinDriver`) the probe forward is
    routed through the Pallas PTC kernel (``kernels.ptc_block_matmul``,
    the production serve-path dataflow: per-block V* → Σ → U on the
    MXU); otherwise through the XLA einsum of the same physics.
    """
    spec = un.mesh_spec(k, kind)
    t = spec.n_rot

    @functools.partial(jax.jit, static_argnums=(4, 5))
    def fwd(phi, sigma, dev, x, start, stop):
        phi, sigma, dev = _scope(phi, sigma, dev, start, stop)
        if use_kernels:
            from ..kernels import ops as kops
            u, v = realized_unitaries(spec, phi[:, :t], phi[:, t:], dev,
                                      model)
            # per-block probe = the PTC kernel on a (B, 1) block grid
            y = kops.ptc_block_matmul(x, u[:, None], sigma[:, None],
                                      v[:, None])          # (n, B·k)
            return jnp.transpose(
                y.reshape(x.shape[0], stop - start, k), (1, 0, 2))
        return jnp.einsum(
            "bij,nj->bni", realized_blocks(spec, phi, sigma, dev, model), x)

    @functools.partial(jax.jit, static_argnums=(2, 3))
    def readback(phi, dev, start, stop):
        dev = jax.tree_util.tree_map(lambda a: a[start:stop], dev)
        phi = phi[start:stop]
        return realized_unitaries(spec, phi[:, :t], phi[:, t:], dev, model)

    @functools.partial(jax.jit, static_argnums=(4, 5))
    def fwd_many(phi, sigma, dev, xs, start, stop):
        # N same-shape probe ops in one compiled call, vmapped over the
        # op axis — bit-identical to N separate fwd calls (each output
        # element's contraction is unchanged; the conformance suite
        # asserts it) at ~1/30 the per-op dispatch cost
        return jax.vmap(
            lambda x: fwd(phi, sigma, dev, x, start, stop))(xs)

    return fwd, readback, fwd_many


@functools.lru_cache(maxsize=256)
def _jitted_layer(k: int, kind: str, model: NoiseModel, m_out: int,
                  use_kernels: bool):
    """Compiled serve-path graph, keyed additionally on the output dim —
    each tenant geometry compiles once and is shared fleet-wide.  On TPU
    the assembled P×Q grid forward runs through the Pallas PTC kernel."""
    spec = un.mesh_spec(k, kind)
    t = spec.n_rot

    @functools.partial(jax.jit, static_argnums=(4, 5))
    def layer(phi, sigma, dev, x, start, stop):
        phi, sigma, dev = _scope(phi, sigma, dev, start, stop)
        if use_kernels:
            from ..kernels import ops as kops
            b = stop - start
            p = -(-m_out // k)
            q = b // p
            u, v = realized_unitaries(spec, phi[:, :t], phi[:, t:], dev,
                                      model)
            xf = x.reshape((-1, x.shape[-1]))
            n = q * k
            if xf.shape[-1] != n:
                xf = jnp.pad(xf, [(0, 0), (0, n - xf.shape[-1])])
            y = kops.ptc_block_matmul(
                xf, u.reshape(p, q, k, k), sigma.reshape(p, q, k),
                v.reshape(p, q, k, k))                     # (T, p·k)
            return y[:, :m_out].reshape(x.shape[:-1] + (m_out,))
        return chip_forward(spec, phi, sigma, dev, model, x, m_out)

    return layer


class TwinDriver(PhotonicDriver):
    """In-process digital twin behind the control-plane ABC."""

    def __init__(self, dev: DeviceRealization, k: int, model: NoiseModel,
                 kind: str = "clements", m: int | None = None,
                 n: int | None = None, drift: DriftConfig | None = None,
                 drift_key: jax.Array | None = None,
                 use_kernels: bool | None = None):
        self._spec = un.mesh_spec(k, kind)
        self._kind = kind
        self._model = model
        self._state = init_drift(dev)
        self._drift_cfg = drift
        self._drift_key = (drift_key if drift_key is not None
                           else jax.random.PRNGKey(0))
        b = int(dev.d_u.shape[0])
        t = self._spec.n_rot
        self._b = b
        self._phi = jnp.zeros((b, 2 * t), jnp.float32)
        self._sigma = jnp.ones((b, k), jnp.float32)
        # default layer geometry: a 1×B grid (calibration-style chips)
        self._m = int(m) if m is not None else k
        self._n = int(n) if n is not None else k * b
        self._stats = DriverStats()
        # route the forward paths through the Pallas PTC kernel (the
        # production dataflow) on a TPU wherever the kernel lowers at this
        # k; XLA einsum otherwise — interpret-mode Pallas would undo the
        # fast path on CPU hosts.  Forcing the kernel at a k it refuses is
        # an error, never a silent einsum.
        if use_kernels is None:
            use_kernels = (jax.default_backend() == "tpu"
                           and kernel_accepts(k))
        elif use_kernels and not kernel_accepts(k):
            raise ValueError(f"the Pallas PTC kernel does not lower at "
                             f"k={k} (needs a multiple of 128)")
        self._use_kernels = bool(use_kernels)
        # jitted probe paths, shared across drivers with the same physics
        # (a fleet of N identical chips compiles each graph once, not N×);
        # block-range scoping is compiled in as a static arg, so each
        # (shape, block_range) signature is a pure cache-hit per call
        self._jit_forward, self._jit_readback, self._jit_forward_many = \
            _jitted_probe_ops(k, kind, model, self._use_kernels)

    def _slice(self, block_range):
        """(start, stop, phi, sigma, dev) scoped to ``block_range``."""
        start, stop = resolve_block_range(self._b, block_range)
        if (start, stop) == (0, self._b):
            return start, stop, self._phi, self._sigma, self._state.dev
        dev = jax.tree_util.tree_map(lambda a: a[start:stop],
                                     self._state.dev)
        return start, stop, self._phi[start:stop], self._sigma[start:stop], \
            dev

    # -- geometry ------------------------------------------------------------

    @property
    def k(self) -> int:
        return self._spec.k

    @property
    def kind(self) -> str:
        return self._kind

    @property
    def n_blocks(self) -> int:
        return self._b

    @property
    def layer_shape(self) -> tuple[int, int]:
        return self._m, self._n

    # -- commanded state -----------------------------------------------------

    def write_phases(self, phi_u: jax.Array, phi_v: jax.Array, *,
                     block_range=None) -> None:
        t = self._spec.n_rot
        start, stop = resolve_block_range(self._b, block_range)
        nb = stop - start
        phi_u = jnp.asarray(phi_u, jnp.float32).reshape(nb, t)
        phi_v = jnp.asarray(phi_v, jnp.float32).reshape(nb, t)
        phi = jnp.concatenate([phi_u, phi_v], axis=-1)
        self._phi = phi if nb == self._b else \
            self._phi.at[start:stop].set(phi)

    def write_sigma(self, sigma: jax.Array, *, block_range=None) -> None:
        start, stop = resolve_block_range(self._b, block_range)
        sigma = jnp.asarray(sigma, jnp.float32).reshape(stop - start, self.k)
        self._sigma = sigma if stop - start == self._b else \
            self._sigma.at[start:stop].set(sigma)

    def write_signs(self, d_u: jax.Array, d_v: jax.Array, *,
                    block_range=None) -> None:
        start, stop = resolve_block_range(self._b, block_range)
        nb = stop - start
        d_u = jnp.asarray(d_u, jnp.float32).reshape(nb, self.k)
        d_v = jnp.asarray(d_v, jnp.float32).reshape(nb, self.k)
        if nb != self._b:
            d_u = self._state.dev.d_u.at[start:stop].set(d_u)
            d_v = self._state.dev.d_v.at[start:stop].set(d_v)
        # signs are topological: they configure both the live device and
        # the drift anchor (OU never walks them)
        self._state = DriftState(
            anchor=self._state.anchor._replace(d_u=d_u, d_v=d_v),
            dev=self._state.dev._replace(d_u=d_u, d_v=d_v),
            t=self._state.t)

    def read_phases(self) -> tuple[jax.Array, jax.Array]:
        t = self._spec.n_rot
        return self._phi[:, :t], self._phi[:, t:]

    def read_sigma(self) -> jax.Array:
        return self._sigma

    # -- probes --------------------------------------------------------------

    def forward(self, x: jax.Array, category: str = "probe", *,
                block_range=None) -> jax.Array:
        x = jnp.asarray(x, jnp.float32)
        start, stop = resolve_block_range(self._b, block_range)
        y = self._jit_forward(self._phi, self._sigma, self._state.dev, x,
                              start, stop)
        self._stats.charge(category, probe_cost(stop - start, x.shape[0]))
        return y

    def forward_layer(self, x: jax.Array, *, block_range=None,
                      out_dim: int | None = None) -> jax.Array:
        x = jnp.asarray(x, jnp.float32)
        start, stop = resolve_block_range(self._b, block_range)
        m_out = int(out_dim) if out_dim is not None else self._m
        layer = _jitted_layer(self.k, self._kind, self._model, m_out,
                              self._use_kernels)
        y = layer(self._phi, self._sigma, self._state.dev, x, start, stop)
        n_cols = int(np.prod(x.shape[:-1])) if x.ndim > 1 else 1
        self._stats.charge("serve", probe_cost(stop - start, n_cols))
        return y

    def forward_many(self, xs, category: str = "probe", *,
                     block_range=None) -> list:
        """Coalesced probe sweep: N same-shape ``forward`` ops in ONE
        compiled (vmapped) call — the data plane of a batched health
        sweep.  Bit-identical to N sequential :meth:`forward` calls
        (asserted by the conformance suite); each op is charged
        individually.  Returns host arrays (one per op).

        ``xs`` is a sequence of same-shape per-op arrays, or the
        equivalent already-stacked (n, ...) array — the form a v4 batch
        frame carries, accepted directly to skip n re-conversions."""
        return list(self.forward_many_stacked(xs, category,
                                              block_range=block_range))

    def forward_many_stacked(self, xs, category: str = "probe", *,
                             block_range=None) -> np.ndarray:
        """:meth:`forward_many` without the final split: returns the
        single stacked ``(n, ...)`` host array — exactly the v4 wire
        form — so a server answering a coalesced probe span avoids
        splitting into n views only to re-stack them for the frame."""
        if isinstance(xs, np.ndarray):
            xs = np.ascontiguousarray(xs, np.float32)
        else:
            xs = np.stack([np.asarray(x, np.float32) for x in xs])
        start, stop = resolve_block_range(self._b, block_range)
        ys = np.asarray(self._jit_forward_many(
            self._phi, self._sigma, self._state.dev, xs, start, stop))
        for x in xs:
            self._stats.charge(category, probe_cost(stop - start, x.shape[0]))
        return ys

    def run_batch(self, ops):
        """Sequential dispatch, with consecutive same-shape ``forward``
        ops coalesced through :meth:`forward_many` (results and meter
        charges are bit-identical to plain sequential execution; the
        merge rule is the shared ``driver.coalesce_spans``).

        ``forward`` results are HOST (numpy) arrays whether or not the
        op happened to coalesce with its neighbors — matching the
        stream transports — so a result's type never depends on an
        invisible batching detail."""
        validate_batch_ops(ops)
        keys = [forward_coalesce_key(kw) if name == "forward" else None
                for name, kw in ops]
        out = []
        for i, j in coalesce_spans(keys):
            if j - i > 1:
                kw = ops[i][1]
                out.extend(self.forward_many(
                    [k.get("x") for _, k in ops[i:j]],
                    category=kw.get("category", "probe"),
                    block_range=kw.get("block_range")))
            else:
                res = super().run_batch([ops[i]])
                if ops[i][0] == "forward":
                    res = [np.asarray(r) for r in res]
                out.extend(res)
        return out

    def readback_bases(self, cols=None, *,
                       block_range=None) -> tuple[jax.Array, jax.Array]:
        start, stop = resolve_block_range(self._b, block_range)
        u, v = self._jit_readback(self._phi, self._state.dev, start, stop)
        if cols is not None:
            idx = jnp.asarray(cols, jnp.int32)
            u, v = u[..., :, idx], v[..., :, idx]
            self._stats.charge("readback",
                               readback_cost(stop - start, int(idx.shape[0])))
        else:
            self._stats.charge("readback",
                               readback_cost(stop - start, self.k))
        return u, v

    # -- in-situ jobs --------------------------------------------------------

    def zo_refine(self, w_blocks: jax.Array, key: jax.Array, cfg: ZOConfig,
                  method: str = "zcd", *, block_range=None) -> ZORefineResult:
        start, stop, phi, sigma, dev = self._slice(block_range)
        res = jobs.phase_refine(self._spec, self._model, dev, phi, sigma,
                                jnp.asarray(w_blocks, jnp.float32), key,
                                cfg, method)
        self._phi = res.x if stop - start == self._b else \
            self._phi.at[start:stop].set(res.x)
        # each ZCD step issues ≤2 transfer-matrix evaluations of k columns
        self._stats.charge("search",
                           float(cfg.steps * 2 * (stop - start) * self.k))
        return ZORefineResult(phi=res.x, loss=res.f, history=res.history,
                              steps=int(cfg.steps))

    def run_ic(self, key: jax.Array, sigs: jax.Array, cfg: ZOConfig, *,
               restarts: int = 4, method: str = "zcd") -> ICJobResult:
        sigs = jnp.asarray(sigs, jnp.float32)
        phi, loss, history = jobs.ic_search(
            self._spec, self._model, self._state.dev, key, cfg, sigs,
            method, restarts)
        self._phi = phi
        t = self._spec.n_rot
        u, v = realized_unitaries(self._spec, phi[:, :t], phi[:, t:],
                                  self._state.dev, self._model)
        # one surrogate measurement = k unit-vector probes per Σ_cal
        # setting; ZCD spends ≤2 measurements per step
        self._stats.charge("search", float(
            restarts * cfg.steps * 2 * sigs.shape[0] * self.k * self._b))
        self._stats.charge("readback", readback_cost(self._b, self.k))
        return ICJobResult(phi=phi, u=u, v=v, loss=loss, history=history)

    # -- time ----------------------------------------------------------------

    def advance(self, dt: float = 1.0) -> None:
        if self._drift_cfg is None:
            return
        self._drift_key, sub = jax.random.split(self._drift_key)
        self._state = advance(self._state, dt, sub, self._drift_cfg)

    # -- accounting / escape hatch -------------------------------------------

    @property
    def stats(self) -> DriverStats:
        return self._stats

    def charge(self, category: str, calls: float) -> None:
        self._stats.charge(category, calls)

    def unsafe_twin(self) -> TwinHandle:
        return TwinHandle(self)


def make_twin(key: jax.Array, n_blocks: int, k: int, model: NoiseModel,
              kind: str = "clements", *, m: int | None = None,
              n: int | None = None, drift: DriftConfig | None = None,
              dev: DeviceRealization | None = None,
              use_kernels: bool | None = None) -> TwinDriver:
    """Sample a fresh device (or wrap ``dev``) behind a TwinDriver.

    ``key`` feeds ``sample_device`` exactly as the pre-driver code did
    (seed-stable with the legacy IC/PM paths); the drift chain derives
    from the same key so one seed pins the whole chip trajectory.
    ``use_kernels`` forces the Pallas forward routing on/off (default:
    auto — on for TPU backends at a k the kernel lowers).
    """
    if dev is None:
        dev = sample_device(key, (n_blocks,), k, model, kind)
    return TwinDriver(dev, k, model, kind, m=m, n=n, drift=drift,
                      drift_key=jax.random.fold_in(key, 0x0D21F7),
                      use_kernels=use_kernels)
