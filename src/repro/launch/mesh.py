"""Production meshes.  Functions, not module constants — importing this
module never touches jax device state."""
from __future__ import annotations

from ..dist.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (possibly fake) devices exist."""
    return make_mesh((data, model), ("data", "model"))
