"""Production mesh construction.

Pure function (no module-level jax device access) so importing never locks
the device count. Single-pod: (16, 16) = ("data", "model"), 256 chips.
Multi-pod: (2, 16, 16) = ("pod", "data", "model"), 512 chips — the "pod"
axis is the IPLS replica axis (rho = number of pods).
"""
from __future__ import annotations


import jax
from jax.sharding import AxisType, Mesh


def _auto(n: int) -> tuple:
    # jax.make_mesh defaults to Explicit axes; the launcher's shardings are
    # with_sharding_constraint / in_shardings rules, which need Auto axes
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_smoke_mesh():
    """1-device mesh with the production axis names — smoke tests exercise the
    same sharding code paths without fake devices."""
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=_auto(2))


def make_data_mesh(n: int) -> Mesh:
    """("data"=n, "model"=1) mesh over the first n devices: the IPLS agent
    axis spread over n chips of one host."""
    return jax.make_mesh(
        (n, 1), ("data", "model"), axis_types=_auto(2), devices=jax.devices()[:n]
    )


def dp_axes(mesh) -> tuple:
    """The data-parallel (IPLS agent) axes for this mesh."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def make_rules(mesh, shape_kind: str, long_context: bool = False) -> dict:
    """Logical->mesh rules for a given mesh and execution shape.

    train:   batch over all DP axes; sequence-parallel activations over model.
    prefill: same as train (forward only).
    decode:  batch over DP axes; KV sequence context-parallel over model —
             and over (data, model) for the batch=1 long-context shape.
    """
    dp = dp_axes(mesh)
    batch_axes = dp if len(dp) > 1 else dp[0]
    rules: dict = {"batch": batch_axes}
    if shape_kind == "decode":
        rules["kv_seq"] = ("data", "model") if long_context else "model"
        rules["act_seq"] = None  # single-token activations
    return rules
