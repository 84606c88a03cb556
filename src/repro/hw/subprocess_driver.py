"""`SubprocessDriver`: op-stream client to a child twin server over pipes.

The hardware-in-the-loop transport: the device (a ``repro.hw.server``
process hosting a TwinDriver) lives outside this interpreter, and the
control plane reaches it only through the wire protocol — the same
topology a lab instrument server or a remote chip simulator would have.
Results are bit-identical to :class:`TwinDriver` for equal construction
seeds when the parent also runs on the CPU (the server runs the same
physics and job code on the CPU backend, ``server_env``; raw array
bytes round-trip the stream exactly).

All protocol behavior (v4 binary frames with the v3 fallback, batch
frames, write pipelining, the async reader, per-op encode/decode) lives
in the shared :class:`~repro.hw.stream_driver.StreamDriver` base; this
class only owns the child process and its (binary) stdin/stdout pipes.
"""

from __future__ import annotations

import os
import subprocess
import sys
import tempfile
from pathlib import Path

import jax

from ..core.noise import NoiseModel
from .drift import DriftConfig
from .stream_driver import StreamDriver, RemoteTwinHandle  # noqa: F401

__all__ = ["SubprocessDriver", "RemoteTwinHandle"]


def _src_root() -> str:
    # .../src/repro/hw/subprocess_driver.py → .../src
    return str(Path(__file__).resolve().parents[2])


def server_env() -> dict:
    """Environment for a spawned twin server: import path, matching
    precision regime (or results stop being bit-identical across
    transports), and the CPU backend.  The server models a remote
    photonic device and never needs an accelerator; on a TPU host the
    parent process holds the chip, and a child that reached for it
    would fail or hang."""
    env = dict(os.environ)
    env["PYTHONPATH"] = _src_root() + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_ENABLE_X64"] = "1" if jax.config.jax_enable_x64 else "0"
    env["JAX_PLATFORMS"] = "cpu"
    return env


def stderr_tail(spool, n: int = 2000) -> str:
    """Diagnostic tail of a spawned server's stderr spool file (shared
    by every transport that hosts a server child)."""
    if spool is None:
        return ""
    try:
        spool.flush()
        with open(spool.name) as f:
            tail = f.read()[-n:]
    except OSError:
        return ""
    return "\nserver stderr tail:\n" + tail


class SubprocessDriver(StreamDriver):
    """Control-plane client to a ``repro.hw.server`` child process."""

    def __init__(self, key: jax.Array, n_blocks: int, k: int,
                 model: NoiseModel, kind: str = "clements", *,
                 m: int | None = None, n: int | None = None,
                 drift: DriftConfig | None = None,
                 python: str | None = None, protocol: int | None = None):
        self._proc = None
        self._stderr = None
        try:
            # server stderr (jax chatter, crash tracebacks) goes to a
            # spool file so a dead pipe can be diagnosed without
            # polluting stdout
            self._stderr = tempfile.NamedTemporaryFile(
                mode="w+", prefix="repro-hw-server-", suffix=".err",
                delete=False)
            # binary pipes (the wire is framed bytes, not text); 1 MiB
            # buffers — a batched probe sweep's response frame is
            # ~100 KB, and default 8 KB buffering costs a dozen
            # syscalls per frame on the hot path
            self._proc = subprocess.Popen(
                [python or sys.executable, "-u", "-m", "repro.hw.server"],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                stderr=self._stderr, env=server_env(), bufsize=1 << 20)
            self._fin = self._proc.stdout
            self._fout = self._proc.stdin
            self._handshake(key, n_blocks, k, model, kind, m, n, drift,
                            protocol=protocol)
        except Exception:
            # a half-built driver (spawn failed, handshake refused) must
            # not leak the child or the spool file
            self.close()
            raise

    # -- transport hooks -----------------------------------------------------

    def _transport_alive(self) -> bool:
        return (getattr(self, "_proc", None) is not None
                and self._proc.poll() is None)

    def _transport_diagnostics(self) -> str:
        if getattr(self, "_proc", None) is None:
            return ""
        return stderr_tail(self._stderr)

    def close(self) -> None:
        proc = getattr(self, "_proc", None)
        if proc is not None:
            try:
                if proc.poll() is None:
                    self._shutdown_stream()
                    proc.wait(timeout=5)
            except Exception:
                proc.kill()
                proc.wait(timeout=5)
            self._proc = None
            self._fin = self._fout = None
        if getattr(self, "_stderr", None) is not None:
            try:
                self._stderr.close()
                os.unlink(self._stderr.name)
            except OSError:
                pass
            self._stderr = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
