"""Device mesh construction."""

from __future__ import annotations

import os
import re
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def force_cpu_host_devices(n: int) -> None:
    """Force the CPU platform with at least ``n`` virtual devices.

    ``XLA_FLAGS`` is only read at backend init — so this must run
    before any other JAX use in the process. Used by
    tests/conftest.py and ``__graft_entry__.dryrun_multichip``.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(_COUNT_FLAG + r"=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = f"{flags} {_COUNT_FLAG}={n}".strip()
    elif int(m.group(1)) < n:
        os.environ["XLA_FLAGS"] = flags.replace(
            m.group(0), f"{_COUNT_FLAG}={n}")
    jax.config.update("jax_platforms", "cpu")
    have = len(jax.devices("cpu"))
    if have < n:
        raise RuntimeError(
            f"need {n} virtual CPU devices but the JAX CPU backend "
            f"initialized with {have}; force_cpu_host_devices must be "
            "called before any other JAX use in the process")


def make_mesh(
    shape: Optional[Tuple[int, ...]] = None,
    axis_names: Sequence[str] = ("data",),
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a mesh over ``devices`` (default: all).

    ``shape=None`` puts every device on the first axis. For a 2-axis
    layout (DP × EP) pass e.g. ``shape=(4, 2),
    axis_names=("data", "expert")``.
    """
    devs = list(devices if devices is not None else jax.devices())
    if shape is None:
        shape = (len(devs),) + (1,) * (len(axis_names) - 1)
    n = int(np.prod(shape))
    if n > len(devs):
        raise ValueError(f"mesh shape {shape} needs {n} devices, "
                         f"have {len(devs)}")
    grid = np.array(devs[:n]).reshape(shape)
    return Mesh(grid, tuple(axis_names))


def mesh_from_config(pcfg, devices: Optional[Sequence] = None) -> Mesh:
    """Build the mesh a :class:`~cilium_tpu.core.config.ParallelConfig`
    describes — the TOML/env-driven face of :func:`make_mesh`:
    ``data_axis`` (DP over the flow batch), plus ``expert_axis`` (EP
    over DFA banks) when ``use_expert_axis`` is set; ``mesh_shape``
    pins the layout (None → every device on the data axis)."""
    axes = ((pcfg.data_axis, pcfg.expert_axis)
            if pcfg.use_expert_axis else (pcfg.data_axis,))
    shape = pcfg.mesh_shape
    if shape is not None:
        shape = tuple(shape)
        if len(shape) != len(axes):
            raise ValueError(
                f"mesh_shape {shape} has {len(shape)} axes but the "
                f"config names {len(axes)} ({axes})")
    return make_mesh(shape, axes, devices)


def mesh_from_root_config(cfg, devices: Optional[Sequence] = None) -> Mesh:
    """:func:`mesh_from_config` off a root ``Config`` (its
    ``parallel`` section)."""
    return mesh_from_config(cfg.parallel, devices)


def data_parallel_mesh(n: Optional[int] = None) -> Mesh:
    devs = jax.devices()
    if n is not None:
        devs = devs[:n]
    return make_mesh((len(devs),), ("data",), devs)
