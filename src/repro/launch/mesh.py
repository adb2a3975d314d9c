"""Production mesh builders.

Functions (never module-level constants) so importing this module does not
touch jax device state — the 512-placeholder-device XLA flag must be set by
the *entry point* (dryrun.py) before any jax initialization.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """v5e-256 pod: (data=16, model=16); two pods: (pod=2, data=16, model=16).

    The `pod` axis composes with `data` for DP (the gradient all-reduce is the
    only DCN-crossing collective) and can be re-purposed as a pipeline axis
    (repro.dist.pipeline).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over the actually-present devices (tests/examples)."""
    return _auto_mesh((data, model), ("data", "model"))


def _auto_mesh(shape, axes):
    # Auto axes: the sharding rules (dist/sharding.py) place arrays with
    # NamedShardings and let XLA propagate; jax.make_mesh's default Explicit
    # axes would instead demand an out_sharding on every gather.
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


# TPU v5e hardware constants for the roofline terms (per chip).
HW = {
    "name": "tpu-v5e",
    "peak_bf16_flops": 197e12,     # FLOP/s
    "peak_int8_ops": 394e12,       # OP/s (MXU int8 = 2x bf16)
    "hbm_bytes_per_s": 819e9,      # HBM bandwidth
    "ici_bytes_per_s_per_link": 50e9,
    "ici_links": 4,                # 2D torus on v5e
    "hbm_bytes": 16 * 1024**3,
}
