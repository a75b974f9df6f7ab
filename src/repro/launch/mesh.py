"""Production mesh construction.

Functions, not module-level constants — importing this module never
touches jax device state (jax locks the device count at first backend
init, and smoke tests must see 1 CPU device while the dry-run sees 512).
Every axis is ``Auto``: the models place tensors through GSPMD sharding
constraints (``repro.dist.api.constrain``), not through explicitly
typed shardings.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.dist.api import MeshRules

__all__ = ["make_production_mesh", "rules_for_mesh", "make_local_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod stacks 2 pods = 512 chips.

    Axes: ("pod",) data-parallel across DCI; "data" = in-pod DP (+ZeRO-1);
    "model" = TP/EP/SP."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def rules_for_mesh(mesh, sequence_parallel: bool = True) -> MeshRules:
    """Production rules: sequence parallelism ON by default — the
    residual stream between blocks is sharded over the model axis, which
    divides the scan-carry activation history by 16x (without it the
    dense train cells exceed per-chip HBM; see EXPERIMENTS.md §Perf)."""
    import dataclasses

    rules = MeshRules()
    if "pod" in mesh.shape:
        rules = rules.multipod()
    # production posture: ZeRO-3 params (scan-FSDP) + sequence parallelism
    rules = dataclasses.replace(rules, fsdp=True)
    if sequence_parallel:
        rules = dataclasses.replace(rules, sp="model")
    return rules


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests)."""
    return jax.make_mesh(
        (data, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
