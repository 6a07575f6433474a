"""Production mesh construction (+ elastic re-derivation).

`make_production_mesh` is a FUNCTION (importing this module never touches
jax device state).  Single-pod: 16×16 = 256 chips; multi-pod: 2×16×16 = 512.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh

from repro.distributed.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_from_devices(devices=None, model_parallel: int = 0) -> Mesh:
    """Elastic mesh: factor whatever devices are alive into (data, model).

    Used on restart after node loss — checkpoints are topology-agnostic, so
    training resumes on the surviving fleet (DESIGN.md §7).
    """
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if model_parallel <= 0:
        # largest power-of-two model axis ≤ sqrt(n) that divides n
        model_parallel = 1
        m = 1
        while m * 2 <= n and n % (m * 2) == 0 and (m * 2) ** 2 <= n:
            m *= 2
        model_parallel = m
    assert n % model_parallel == 0, (n, model_parallel)
    return make_mesh((n // model_parallel, model_parallel),
                     ("data", "model"), devices=devices)


def mesh_axis_size(mesh: Optional[Mesh], name: str) -> int:
    if mesh is None:
        return 1
    return mesh.shape.get(name, 1)
