"""PhotonicDriver conformance suite.

Parametrized over the three shipped transports (in-process
``TwinDriver``, JSON-over-pipe ``SubprocessDriver``, and TCP
``SocketDriver``): a scripted control-plane session must produce
*bit-identical* results on all — same physics, same seeds, same
backend — and the PTC-call meter must charge exactly the Appendix-G
costs (including ops shipped inside a v3 ``batch`` frame, which are
metered individually).  The tenant-addressable session exercises every
``block_range``-scoped op (v2 protocol surface) the same way, including
scoped-write/whole-read consistency.  Plus the guard test: control-plane
modules (``repro.runtime``, ``core.calibration``, ``core.mapping``)
must never touch twin internals except through the audited
``unsafe_twin()`` escape hatch.

(Protocol v3 framing — batch round-trips, pipelining flush order,
malformed/oversized-frame rejection — is covered by
``tests/test_protocol_v3.py``.)
"""

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.noise import DEFAULT_NOISE
from repro.core.calibration import calibrate_identity
from repro.core.mapping import parallel_map
from repro.optim.zo import ZOConfig
from repro.hw import make_driver, make_twin, TwinUnavailable
from repro.hw.drift import DriftConfig
from repro.hw.driver import PhotonicDriver
from repro.runtime.recalibrate import RecalConfig, recalibrate

K = 3
M = N = 6
B = (M // K) * (N // K)          # 4 blocks
MODEL = DEFAULT_NOISE.post_ic()
DRIFT = DriftConfig(sigma_phase=0.03, theta=0.01)
TRANSPORTS = ["twin", "subprocess", "socket"]
STREAM_TRANSPORTS = ["subprocess", "socket"]

KEY = jax.random.PRNGKey(42)


def _mk(transport):
    return make_driver(transport, KEY, B, K, MODEL, m=M, n=N, drift=DRIFT)


def _reference_twin():
    return make_twin(KEY, B, K, MODEL, m=M, n=N, drift=DRIFT)


def _blocks(seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((B, K, K)) * 0.4, jnp.float32)


def _session(driver) -> dict:
    """One scripted control-plane session exercising every ABC op."""
    rng = np.random.default_rng(7)
    t = driver.read_phases()[0].shape[-1]
    pu = jnp.asarray(rng.uniform(0, 1, (B, t)), jnp.float32)
    pv = jnp.asarray(rng.uniform(0, 1, (B, t)), jnp.float32)
    sg = jnp.asarray(rng.uniform(0.5, 1.5, (B, K)), jnp.float32)
    du = jnp.asarray(rng.choice([-1.0, 1.0], (B, K)), jnp.float32)
    dv = jnp.asarray(rng.choice([-1.0, 1.0], (B, K)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((5, K)), jnp.float32)
    xl = jnp.asarray(rng.standard_normal((3, N)), jnp.float32)
    w = _blocks(1)

    out = {}
    driver.write_signs(du, dv)
    driver.write_phases(pu, pv)
    driver.write_sigma(sg)
    out["phi_u"], out["phi_v"] = driver.read_phases()
    out["sigma"] = driver.read_sigma()
    out["fwd"] = driver.forward(x)
    out["layer"] = driver.forward_layer(xl)
    res = driver.zo_refine(w, jax.random.PRNGKey(3),
                           ZOConfig(steps=30, inner=12, delta0=0.1,
                                    decay=1.05))
    out["zo_phi"], out["zo_loss"] = res.phi, res.loss
    out["u"], out["v"] = driver.readback_bases()
    for _ in range(5):
        driver.advance(1.0)
    out["fwd_drifted"] = driver.forward(x)
    out["true_d"] = driver.unsafe_twin().true_mapping_distance(w)
    out["stats"] = driver.stats.as_dict()
    return out


@pytest.mark.parametrize("transport", TRANSPORTS)
def test_scripted_session_matches_reference_twin(transport):
    """Every op's result is bit-identical to the in-process twin run
    from the same construction seed (float32 survives the pipe exactly;
    jobs execute the same code on the same backend)."""
    driver = _mk(transport)
    try:
        got = _session(driver)
    finally:
        driver.close()
    ref = _session(_reference_twin())
    for name in ("phi_u", "phi_v", "sigma", "fwd", "layer", "zo_phi",
                 "zo_loss", "u", "v", "fwd_drifted"):
        np.testing.assert_array_equal(np.asarray(ref[name]),
                                      np.asarray(got[name]), err_msg=name)
    assert got["true_d"] == ref["true_d"]
    assert got["stats"] == ref["stats"]


def _tenant_session(driver) -> dict:
    """A scripted MULTI-TENANT control-plane session: two tenants on one
    chip (blocks [0, 4) and [4, 6) when B=6... here B=4 → [0, 3)/[3, 4)),
    exercising every block_range-scoped op of the v2 surface."""
    rng = np.random.default_rng(11)
    t = driver.read_phases()[0].shape[-1]
    br0, br1 = (0, 3), (3, B)
    b0, b1 = 3, B - 3
    out = {}
    # scoped writes: tenant 0 then tenant 1, different states
    driver.write_signs(
        jnp.asarray(rng.choice([-1.0, 1.0], (b0, K)), jnp.float32),
        jnp.asarray(rng.choice([-1.0, 1.0], (b0, K)), jnp.float32),
        block_range=br0)
    driver.write_phases(
        jnp.asarray(rng.uniform(0, 1, (b0, t)), jnp.float32),
        jnp.asarray(rng.uniform(0, 1, (b0, t)), jnp.float32),
        block_range=br0)
    driver.write_sigma(
        jnp.asarray(rng.uniform(0.5, 1.5, (b0, K)), jnp.float32),
        block_range=br0)
    driver.write_phases(
        jnp.asarray(rng.uniform(0, 1, (b1, t)), jnp.float32),
        jnp.asarray(rng.uniform(0, 1, (b1, t)), jnp.float32),
        block_range=br1)
    driver.write_sigma(
        jnp.asarray(rng.uniform(0.5, 1.5, (b1, K)), jnp.float32),
        block_range=br1)
    # whole-chip reads see the per-tenant writes landed in place
    out["phi_u"], out["phi_v"] = driver.read_phases()
    out["sigma"] = driver.read_sigma()
    # scoped probes + scoped serve path
    x = jnp.asarray(rng.standard_normal((4, K)), jnp.float32)
    out["fwd0"] = driver.forward(x, block_range=br0)
    out["fwd1"] = driver.forward(x, block_range=br1)
    xl = jnp.asarray(rng.standard_normal((2, b1 * K)), jnp.float32)
    out["layer1"] = driver.forward_layer(xl, block_range=br1, out_dim=K)
    # scoped in-situ job (the partial-recal primitive): tenant 0 only
    w0 = jnp.asarray(rng.standard_normal((b0, K, K)) * 0.4, jnp.float32)
    res = driver.zo_refine(w0, jax.random.PRNGKey(5),
                           ZOConfig(steps=20, inner=12, delta0=0.1,
                                    decay=1.05), block_range=br0)
    out["zo_phi"] = res.phi
    out["u1"], out["v1"] = driver.readback_bases(block_range=br1)
    out["u0_cols"], _ = driver.readback_bases(cols=[0, 2], block_range=br0)
    for _ in range(4):
        driver.advance(1.0)
    out["fwd0_drifted"] = driver.forward(x, block_range=br0)
    out["true0"] = driver.unsafe_twin().true_mapping_distance(w0, br0)
    out["stats"] = driver.stats.as_dict()
    return out


@pytest.mark.parametrize("transport", TRANSPORTS)
def test_tenant_session_matches_reference_twin(transport):
    """Every tenant-scoped op is bit-identical across transports (the
    v2 wire protocol forwards block ranges losslessly)."""
    driver = _mk(transport)
    try:
        got = _tenant_session(driver)
    finally:
        driver.close()
    ref = _tenant_session(_reference_twin())
    for name in ("phi_u", "phi_v", "sigma", "fwd0", "fwd1", "layer1",
                 "zo_phi", "u1", "v1", "u0_cols", "fwd0_drifted"):
        np.testing.assert_array_equal(np.asarray(ref[name]),
                                      np.asarray(got[name]), err_msg=name)
    assert got["true0"] == ref["true0"]
    assert got["stats"] == ref["stats"]


@pytest.mark.parametrize("transport", TRANSPORTS)
def test_tenant_scoped_ptc_accounting(transport):
    """Scoped ops charge for the tenant's block count, not the chip's."""
    driver = _mk(transport)
    try:
        driver.reset_stats()
        driver.forward(jnp.ones((5, K)), block_range=(0, 3))
        assert driver.stats.probe == 3 * 5
        driver.readback_bases(block_range=(3, B))
        assert driver.stats.readback == 2 * (B - 3) * K
        driver.forward_layer(jnp.ones((7, K)), block_range=(3, B),
                             out_dim=K)
        assert driver.stats.serve == (B - 3) * 7
        steps = 5
        driver.zo_refine(_blocks()[:3], jax.random.PRNGKey(0),
                         ZOConfig(steps=steps, inner=6, delta0=0.1,
                                  decay=1.05), block_range=(0, 3))
        assert driver.stats.search == steps * 2 * 3 * K
    finally:
        driver.close()


@pytest.mark.parametrize("transport", TRANSPORTS)
def test_block_range_bounds_rejected(transport):
    """Out-of-bounds tenant ranges are a hard error on every transport."""
    driver = _mk(transport)
    try:
        for bad in ((0, B + 1), (-1, 2), (2, 2), (3, 1)):
            with pytest.raises((ValueError, RuntimeError)):
                driver.forward(jnp.ones((2, K)), block_range=bad)
    finally:
        driver.close()


@pytest.mark.parametrize("peer_version", [1, 2])
def test_protocol_version_handshake_rejects_mismatch(peer_version):
    """A v1 or v2 client is refused by the server (which speaks v3 and
    v4) — no silent fallback onto a surface it would misread (a v2 peer
    would treat a ``batch`` frame as an unknown op mid-session)."""
    import io
    from repro.hw.protocol import encode, PROTOCOL_VERSION, SUPPORTED_VERSIONS
    from repro.hw.server import serve

    assert PROTOCOL_VERSION == 4
    assert peer_version not in SUPPORTED_VERSIONS
    req = {"id": 1, "op": "init", "kw": encode(dict(
        v=peer_version, key=np.zeros(2, np.uint32), n_blocks=B, k=K,
        model=dict(), drift=None))}
    import json as _json
    fin = io.BytesIO((_json.dumps(req) + "\n").encode())
    fout = io.BytesIO()
    serve(fin, fout)
    resp = _json.loads(fout.getvalue().splitlines()[0])
    assert resp["ok"] is False
    assert "protocol mismatch" in resp["error"]


@pytest.mark.parametrize("transport", TRANSPORTS)
def test_batch_ops_metered_individually(transport):
    """PTC-call metering counts every op INSIDE a batch frame at its
    full Appendix-G charge — one batch ≠ one PTC call (regression: a
    transport must not meter the frame instead of its ops)."""
    driver = _mk(transport)
    try:
        driver.reset_stats()
        x = jnp.ones((5, K))
        _ = driver.run_batch([
            ("forward", dict(x=x)),
            ("forward", dict(x=x)),
            ("forward", dict(x=x, block_range=(0, 3))),
            ("readback_bases", {}),
        ])
        s = driver.stats
        assert s.probe == 2 * B * 5 + 3 * 5       # each forward charged
        assert s.readback == 2 * B * K
    finally:
        driver.close()


@pytest.mark.parametrize("transport", TRANSPORTS)
def test_ic_pm_recal_identical_across_transports(transport):
    """The three control-plane flows (IC, PM, closed-loop recal) return
    identical results over any transport."""
    # IC on a fresh device (driver-generic entry point)
    ic_cfg = ZOConfig(steps=40, inner=12, delta0=0.5, decay=1.05)
    d1 = _mk(transport)
    try:
        ic = calibrate_identity(KEY, B, K, MODEL, cfg=ic_cfg, restarts=2,
                                driver=d1)
    finally:
        d1.close()
    ic_ref = calibrate_identity(KEY, B, K, MODEL, cfg=ic_cfg, restarts=2,
                                driver=_reference_twin())
    np.testing.assert_array_equal(np.asarray(ic_ref.phi_u),
                                  np.asarray(ic.phi_u))
    np.testing.assert_array_equal(np.asarray(ic_ref.mse_u),
                                  np.asarray(ic.mse_u))

    # PM deployment + drift + recalibration on the same chip
    rng = np.random.default_rng(5)
    w = jnp.asarray(rng.standard_normal((M, N)) / np.sqrt(M), jnp.float32)
    pm_cfg = ZOConfig(steps=30, inner=12, delta0=0.2, decay=1.05)

    def flow(driver):
        pm = parallel_map(KEY, w, K, MODEL, cfg=pm_cfg, driver=driver)
        for _ in range(30):
            driver.advance(1.0)
        rc = recalibrate(jax.random.PRNGKey(9), driver, _blocks(1),
                         RecalConfig(zo_steps=40, delta0=0.05))
        return pm, rc

    d2 = _mk(transport)
    try:
        pm, rc = flow(d2)
    finally:
        d2.close()
    pm_ref, rc_ref = flow(_reference_twin())
    np.testing.assert_array_equal(np.asarray(pm_ref.err_osp),
                                  np.asarray(pm.err_osp))
    np.testing.assert_array_equal(np.asarray(pm_ref.phi_u),
                                  np.asarray(pm.phi_u))
    np.testing.assert_array_equal(np.asarray(rc_ref.phi),
                                  np.asarray(rc.phi))
    np.testing.assert_array_equal(np.asarray(rc_ref.sigma),
                                  np.asarray(rc.sigma))
    assert float(rc_ref.dist_after) == float(rc.dist_after)
    assert rc_ref.ptc_calls == rc.ptc_calls


@pytest.mark.parametrize("transport", TRANSPORTS)
def test_ptc_call_accounting(transport):
    """The driver meters exactly the Appendix-G charges per op."""
    driver = _mk(transport)
    try:
        driver.reset_stats()
        assert driver.stats.total == 0.0

        driver.forward(jnp.ones((5, K)))
        assert driver.stats.probe == B * 5           # E_fwd = B·n_cols

        driver.readback_bases()
        assert driver.stats.readback == 2 * B * K    # 2 reciprocal passes

        driver.forward_layer(jnp.ones((7, N)))
        assert driver.stats.serve == B * 7

        steps = 10
        driver.zo_refine(_blocks(), jax.random.PRNGKey(0),
                         ZOConfig(steps=steps, inner=6, delta0=0.1,
                                  decay=1.05))
        assert driver.stats.search == steps * 2 * B * K

        driver.charge("probe", 3.5)                  # controller-side meter
        assert driver.stats.probe == B * 5 + 3.5
        assert driver.stats.total == (B * 5 + 3.5 + 2 * B * K + B * 7
                                      + steps * 2 * B * K)
        driver.reset_stats()
        assert driver.stats.total == 0.0
    finally:
        driver.close()


def test_twin_kernel_route_matches_einsum_at_k128():
    """The Pallas PTC route (interpret mode off-TPU) gives the einsum
    route's probe and layer outputs at k=128, over token counts that are
    not multiples of 8."""
    k, p, q = 128, 2, 3
    key = jax.random.PRNGKey(7)
    kern, plain = (make_twin(key, p * q, k, MODEL, m=p * k, n=q * k,
                             use_kernels=flag) for flag in (True, False))
    for drv in (kern, plain):
        drv.write_sigma(jnp.linspace(0.5, 1.5, p * q * k))
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((5, q * k)), jnp.float32)
    y = np.asarray(kern.forward_layer(x))
    assert y.shape == (5, p * k)
    np.testing.assert_allclose(y, np.asarray(plain.forward_layer(x)),
                               rtol=1e-4, atol=1e-4)
    xp = jnp.asarray(rng.standard_normal((11, k)), jnp.float32)
    np.testing.assert_allclose(np.asarray(kern.forward(xp)),
                               np.asarray(plain.forward(xp)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("k", [4, 9, 64])
def test_twin_refuses_kernel_route_where_it_cannot_lower(k):
    """Forcing the kernel at a k Mosaic refuses is an error; the default
    route never picks it there."""
    with pytest.raises(ValueError, match="multiple of 128"):
        make_twin(KEY, 2, k, MODEL, use_kernels=True)
    assert not make_twin(KEY, 2, k, MODEL)._use_kernels


def test_unsafe_twin_raises_without_twin_backing():
    """A driver not backed by an inspectable twin refuses the hatch."""

    class HardwareDriver(PhotonicDriver):
        k = 3
        kind = "clements"
        n_blocks = 1
        layer_shape = (3, 3)

        def write_phases(self, *a):
            pass

        write_sigma = write_signs = write_phases

        def read_phases(self):
            return None, None

        def read_sigma(self):
            return None

        def forward(self, x, category="probe"):
            return x

        forward_layer = read_sigma

        def readback_bases(self):
            return None, None

        def zo_refine(self, *a, **k):
            raise NotImplementedError

        run_ic = zo_refine

        def advance(self, dt=1.0):
            pass

        stats = property(lambda self: None)

        def charge(self, *a):
            pass

    with pytest.raises(TwinUnavailable):
        HardwareDriver().unsafe_twin()


# ---------------------------------------------------------------------------
# guard: control-plane modules stay on the legal surface
# ---------------------------------------------------------------------------

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"


def test_control_plane_never_imports_twin_internals():
    # The old line-regex guard that lived here grew into the RPL1xx
    # analyzers of repro.analysis (AST-accurate, covers every spelling,
    # audits unsafe_twin call sites).  This is the thin assertion that
    # the whole source tree has zero twin-boundary findings.
    from repro.analysis import run_lint

    assert SRC.is_dir(), "guard scope is empty — layout changed?"
    result = run_lint([str(SRC)], codes=["RPL101", "RPL102", "RPL103"])
    assert not result.errors, result.errors
    offenders = [f.format() for f in result.findings]
    assert not offenders, (
        "control-plane code reached into twin internals outside "
        "unsafe_twin():\n" + "\n".join(offenders))
