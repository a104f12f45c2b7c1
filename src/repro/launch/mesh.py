"""Production mesh builders (TPU v5e pods; CPU placeholder devices for
the dry-run).  Functions, not module constants, so importing never
touches jax device state."""
from __future__ import annotations

import jax


def _auto_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis ``AxisType.Auto`` (shardings
    are propagated, not carried in types)."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_im_mesh(num_machines: int, *, multi_pod: bool = False):
    """Single 'machines' axis mesh for the GreediRIS rounds (the
    algorithm is 1-D: every chip is a RandGreedi machine).  With
    multi_pod the same chips are named ('pod', 'machines') so the
    all_to_all/gather spans both axes explicitly."""
    if multi_pod:
        return _auto_mesh((2, num_machines // 2), ("pod", "machines"))
    return _auto_mesh((num_machines,), ("machines",))


def make_host_mesh():
    """Whatever devices exist right now, as a 1-D mesh (CPU tests)."""
    n = len(jax.devices())
    return _auto_mesh((n,), ("machines",))
