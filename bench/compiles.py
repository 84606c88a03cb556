"""XLA compile time and count, as JAX reports them.

Copied from ``chip_smoke.CompileClock`` (a ``jax.monitoring`` listener
on the backend-compile duration event), with a count beside the sum so
that the harness can show no compile falls inside a measured window.
"""

from __future__ import annotations

__all__ = ["CompileClock"]


class CompileClock:
    """Sums XLA backend-compile durations and counts them."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            self.seconds += duration
            self.count += 1
