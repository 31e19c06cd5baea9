"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required for the 512-placeholder-device
dry-run to control initialization order.
"""
from __future__ import annotations

import jax


def _make_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with every axis explicitly Auto."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips).

    When more placeholder devices exist than the mesh needs (the 512-device
    dry-run lowering a single-pod mesh), the leading subset is used.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devs = jax.devices()
    if len(devs) == n:
        return _make_mesh(shape, axes)
    if len(devs) > n:
        import numpy as np

        return jax.sharding.Mesh(np.asarray(devs[:n]).reshape(shape), axes)
    raise RuntimeError(
        f"need {n} devices for mesh {shape}, have {len(devs)} — for a CPU "
        f"rehearsal set XLA_FLAGS=--xla_force_host_platform_device_count={n}"
    )


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over whatever devices exist (tests / examples on CPU)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return _make_mesh((n // model_parallel, model_parallel), ("data", "model"))


def make_sweep_mesh(n_devices: int | None = None):
    """1-D ``("sweep",)`` mesh for device-parallel ``sweep_grid(mesh=...)``.

    Uses the leading ``n_devices`` of whatever exists (all of them by
    default) — real TPUs, or virtual CPU devices under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.  The sweep
    engine splits its flat chunk axis over this mesh with ``shard_map``;
    results are bit-identical for every mesh size, so the device count is
    purely a throughput knob.
    """
    import numpy as np

    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    if n > len(devs):
        raise RuntimeError(
            f"need {n} devices for a sweep mesh, have {len(devs)} — for a CPU "
            f"rehearsal set XLA_FLAGS=--xla_force_host_platform_device_count={n}"
        )
    return jax.sharding.Mesh(np.asarray(devs[:n]), ("sweep",))


def data_axes(mesh) -> tuple:
    """Mesh axes that shard the batch (pod + data when present)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
