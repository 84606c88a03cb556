"""Faults a serving cell can have, planted in the program underneath a
run to show that ``correct`` comes out false.

Each fault takes ``patch(obj, name, value)``, which replaces an
attribute (pytest's ``monkeypatch.setattr``, or ``setattr`` in a process
that runs one fault and ends): the run then drives the program's own
timed path with that part broken.
"""

from __future__ import annotations

__all__ = ["FAULTS", "plant"]


def _engine():
    import repro.serving.engine as engine

    return engine


def _wrap_step(patch, change):
    """Break the gateway step where it is built: ``change(step, params,
    views, batch)`` returns what the engine gets."""
    engine = _engine()
    for name in ("build_gateway_step", "build_gateway_prefill_step"):
        build = getattr(engine, name)

        def broken(cfg, build=build):
            step = build(cfg)
            return lambda p, views, batch: change(step, p, views, batch)

        patch(engine, name, broken)


def state_unchanged(patch):
    """The KV scatter returns the pool as it was: no state is kept."""
    engine = _engine()
    patch(engine, "paged_scatter", lambda i, r, pages: pages)
    patch(engine, "paged_scatter_rows", lambda i, r, pages: pages)


def half_batch(patch):
    """The upper half of the slots attends over an empty view."""
    import jax
    import jax.numpy as jnp

    def change(step, p, views, batch):
        b = batch["token"].shape[0]
        keep = (jnp.arange(b) < b // 2)[None, :, None, None, None]
        views = jax.tree.map(lambda a: jnp.where(keep, a, 0), views)
        return step(p, views, batch)
    _wrap_step(patch, change)


def token_altered(patch):
    """Every emitted token becomes the next id of the vocabulary."""
    import jax.numpy as jnp

    def change(step, p, views, batch):
        logits, new = step(p, views, batch)
        return jnp.roll(logits, 1, axis=-1), new
    _wrap_step(patch, change)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "token_altered": token_altered}


def plant(name: str, patch=setattr) -> None:
    FAULTS[name](patch)
