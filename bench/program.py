"""The system under test, as the benchmark drives it: ``repro.core.sweep``.

This is the only module of the benchmark that imports the program.  It
builds the program's configuration from a configuration file, turns a
generated request into a ``SweepRequest`` with ``backend`` and
``chunk_size`` unset (the engine's own defaults), and splits one call into
the steps the window times: submit (``sweep`` returns before the device is
done), wait, and read back to the host.
"""
from __future__ import annotations

import numpy as np


def build_config(cfg: dict):
    """The program's ``ArbitrationConfig`` for a configuration file."""
    from repro.core.grid import ArbitrationConfig, DWDMGrid, VariationModel

    var = cfg["variations"]
    grid = DWDMGrid(
        n_ch=int(cfg["n_ch"]), grid_spacing=float(cfg["grid_spacing_nm"]),
        ring_bias=float(cfg["ring_bias_nm"]), fsr_mean=float(cfg["fsr_nm"]),
        tr_mean=float(cfg["tr_mean_nm"]),
    )
    return ArbitrationConfig(
        grid=grid,
        var=VariationModel(**{k: float(v) for k, v in var.items()}),
        r_order=tuple(int(v) for v in cfg["ring_order"]),
        s_order=tuple(int(v) for v in cfg["target_order"]),
        max_fsr_alias=int(cfg["max_fsr_alias"]),
    )


def make_request(program_cfg, target: dict, metric: str, req):
    """A ``SweepRequest`` for one generated request."""
    from repro.core import SweepRequest
    from repro.core.sampling import UnitSamples

    return SweepRequest(cfg=program_cfg, units=UnitSamples(**req.units),
                        axes=req.axes, metric=metric, **target)


def submit(sweep_request):
    """Dispatch one request; returns before the device has finished."""
    from repro.core import sweep

    return sweep(sweep_request)


def wait(result) -> None:
    import jax

    jax.block_until_ready(result.data)


def readback(result, metric: str) -> dict:
    """Every result field on the host, as the user gets it (name -> array).
    A policy request's one grid is named after what it holds."""
    data = result.data
    if hasattr(data, "_fields"):
        return {k: np.asarray(getattr(data, k)) for k in data._fields}
    return {"min_tr" if metric == "min_tr" else "afp": np.asarray(data)}
