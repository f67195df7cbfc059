"""Pluggable simulation backends.

>>> from repro.sim.backends import get_backend
>>> get_backend("python")      # reference event-loop engine
>>> get_backend("jax")         # batched vmapped engine (campaign sweeps)
>>> get_backend("jax-pallas")  # same engine, Pallas event core

``get_backend(None)`` resolves the default from the ``REPRO_SIM_BACKEND``
environment variable (falling back to ``python``), so scripts and
subprocess drivers can switch engines without threading a flag through
every call site.  Backends are process-wide singletons — the JAX backend's
schedule caches persist across sweeps.

The JAX engine's sequential event core is itself pluggable
(``REPRO_EVENT_CORE`` / ``JaxBatchedBackend(kernel=...)``): ``jax`` takes
the platform default (the compiled Pallas kernel on TPU, the vmapped
``lax.while_loop`` reference on CPU), ``jax-pallas`` is the same backend
constructed with the Pallas kernel (``repro.kernels.event_loop``).
"""

from __future__ import annotations

import os
from typing import Callable, Dict, Optional, Union

from .base import (EVENT_CAP, BatchResult, InstancePerturb, InstanceSpec,
                   LockstepRequest, SimBackend, combined_pe_scale,
                   needs_closed_form, sigma_scale_of)

_FACTORIES: Dict[str, Callable[[], SimBackend]] = {}
_INSTANCES: Dict[str, SimBackend] = {}

#: env var naming the default backend
BACKEND_ENV = "REPRO_SIM_BACKEND"


def register_backend(name: str, factory: Callable[[], SimBackend]) -> None:
    _FACTORIES[name] = factory


def backend_names():
    return sorted(_FACTORIES)


def get_backend(name: Union[str, SimBackend, None] = None) -> SimBackend:
    """Resolve a backend by name (or pass an instance through)."""
    if isinstance(name, SimBackend):
        return name
    if name is None:
        name = os.environ.get(BACKEND_ENV, "python")
    name = name.lower()
    if name not in _FACTORIES:
        raise ValueError(
            f"unknown simulation backend {name!r}; "
            f"available: {backend_names()}")
    if name not in _INSTANCES:
        _INSTANCES[name] = _FACTORIES[name]()
    return _INSTANCES[name]


def _make_python() -> SimBackend:
    from .python import PythonBackend
    return PythonBackend()


def _make_jax() -> SimBackend:
    from .jax_batched import JaxBatchedBackend
    return JaxBatchedBackend()


def _make_jax_pallas() -> SimBackend:
    from .jax_batched import JaxBatchedBackend
    return JaxBatchedBackend(kernel="pallas")


register_backend("python", _make_python)
register_backend("jax", _make_jax)
register_backend("jax-pallas", _make_jax_pallas)

__all__ = [
    "EVENT_CAP", "BatchResult", "InstancePerturb", "InstanceSpec",
    "LockstepRequest", "SimBackend", "combined_pe_scale", "needs_closed_form",
    "sigma_scale_of", "get_backend", "register_backend", "backend_names",
    "BACKEND_ENV",
]
