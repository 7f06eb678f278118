"""Pluggable array backend for the mapping hot path.

The placement stack is NumPy-first: every public function takes and returns
``np.ndarray`` and the default backend executes the hot kernels with the
vectorized NumPy implementations in :mod:`repro.core.mapping`.  When JAX is
installed (``pip install repro-tofa[jax]``), the ``jax`` backend routes the
same kernels — ``hop_bytes``/``hop_bytes_batch``, ``_pairwise_refine``
swap-gain scoring, ``select_nodes`` frontier growth, ``greedy_placement`` —
through jit-compiled implementations (:mod:`repro.core.mapping_jax`) that
score all candidate placements of TOFA's multi-candidate search in a single
device dispatch and keep the per-(topology, health) distance matrices
device-resident across placements.

Selection (first match wins):

* ``backend.use("jax")`` context manager (tests, benchmarks);
* ``PlacementEngine(backend="jax")`` — the engine wraps each placement call;
* ``REPRO_BACKEND=jax`` environment variable (read at import time);
* default: ``numpy``.

Dtype policy: the NumPy kernels are pinned to float64 (the committed
quality/parity baseline).  The jax backend's dtype is chosen by the
platform.  Off TPU it computes in ``float64`` — with in-tree workloads
every guest weight and route distance is an exactly-representable
integer, so the jitted kernels reproduce the NumPy placements
*bit-for-bit*.  On TPU it computes in ``float32``: the chip has no native
float64 and its compiler refuses every Pallas kernel of the hot path
inside the x64 scope, so placements there are held to quality (hop-bytes)
rather than identity.  An explicit ``dtype=`` (``set_backend("jax",
dtype=...)``, ``use(...)``) still wins.  ``jax.config`` handling lives
here, inside the backend: float64 kernel calls run under a *scoped*
``jax.enable_x64`` context (:meth:`JaxBackend.scope`), so neither call
sites nor the float32 accelerator stack ever see mutated global JAX
state.  Placements are integer node-id arrays on every backend (asserted
in ``tests/test_backend_diff.py``), never floats.

The first construction of the jax backend also fixes JAX's persistent
compilation cache: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads
it itself), else ``.jax_cache/`` at the repository root.

A NumPy-only install never imports JAX: requesting the jax backend without
the optional dependency raises :class:`BackendUnavailableError` and
everything else keeps working with zero behavior change.
"""
from __future__ import annotations

import contextlib
import os
from collections import OrderedDict
from pathlib import Path
from typing import Iterator, Optional

import numpy as np


class BackendUnavailableError(RuntimeError):
    """Requested backend cannot be activated (missing optional dependency)."""


class NumpyBackend:
    """Default backend: the vectorized NumPy kernels run as-is."""

    name = "numpy"
    is_jax = False
    dtype = "float64"          # the NumPy kernels are pinned to float64

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"<backend {self.name} dtype={self.dtype}>"


class JaxBackend:
    """JAX backend: jitted kernels + device-resident distance matrices.

    ``dtype`` selects the compute precision of the jitted kernels
    (placement ids stay integers regardless); :func:`get_backend` lets
    the platform choose (:func:`platform_dtype`).  ``float64`` runs every
    kernel call and device transfer inside a *scoped* ``jax.enable_x64``
    context (:meth:`scope`) — the process-wide ``jax_enable_x64`` flag is
    never touched, so the accelerator stack's float32 world is unaffected
    by placement calls and vice versa (scoped config participates in the
    jit cache key).
    """

    name = "jax"
    is_jax = True

    def __init__(self, dtype: str, max_cached_devices: int = 8,
                 devices: Optional[int] = None):
        try:
            import jax
        except ImportError as e:  # pragma: no cover - exercised on bare envs
            raise BackendUnavailableError(
                "the 'jax' placement backend needs the optional jax "
                "dependency: pip install repro-tofa[jax]") from e
        _init_compile_cache(jax)
        if dtype not in ("float32", "float64"):
            raise ValueError(f"jax backend dtype must be float32|float64, "
                             f"got {dtype!r}")
        self.dtype = dtype
        # cap on the devices the sharded candidate-stack dispatch may
        # use; 0 = all local devices.  REPRO_JAX_DEVICES=1 pins the
        # single-device vmap path on multi-device hosts.
        self.devices = int(_resolve_devices(devices))
        # host ndarray -> device array, LRU by object identity.  The engine
        # hands the same cached D / Eq. 1 weight matrix object to every
        # placement against one (topology, health) state, so identity is
        # exactly the right key: one transfer per health state, then every
        # job in the batch reuses the device-resident copy.
        self._device: OrderedDict[int, tuple[np.ndarray, object]] = \
            OrderedDict()
        self._max_cached = max_cached_devices
        # identity keying composes with the engine's epoch-keyed matrix
        # cache: one (topology, state epoch) == one matrix object == one
        # transfer.  The counters make that contract testable
        # (tests/test_state.py asserts zero new transfers across a warm
        # state-churn sequence); ``numpy_fallbacks`` counts the calls the
        # dispatch layer (``mapping._jax_kernels``) turned away to NumPy.
        self.stats = {"transfers": 0, "sharded_dispatches": 0,
                      "numpy_fallbacks": 0}

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"<backend {self.name} dtype={self.dtype} "
                f"devices={self.devices or 'all'}>")

    @property
    def np_dtype(self):
        return np.float32 if self.dtype == "float32" else np.float64

    @property
    def device_count(self) -> int:
        """Devices visible to the sharded refine dispatch: local device
        count clamped by the ``devices`` cap (0 = uncapped)."""
        import jax
        n = len(jax.local_devices())
        return min(n, self.devices) if self.devices else n

    def scope(self):
        """Context the jitted kernels run under: scoped x64 for the
        float64 dtype policy, a no-op for float32."""
        if self.dtype == "float64":
            import jax
            return jax.enable_x64(True)
        return contextlib.nullcontext()

    def device_matrix(self, arr: np.ndarray):
        """Device-resident copy of a host matrix, cached by identity.

        The host array is kept referenced so ``id()`` cannot be recycled
        while the cache entry lives.  Transfers happen inside
        :meth:`scope` so float64 matrices stay float64.

        A :class:`~repro.core.lazydist.LazyDistance` must never land
        here — densifying it on device would defeat the O(n)-memory
        contract.  The jax mapping layer ships its ``implicit`` coords
        instead (``mapping_jax._device_distances``); anything else is a
        dispatch bug, surfaced eagerly.
        """
        if hasattr(arr, "implicit"):
            raise TypeError(
                "refusing to densify a LazyDistance onto device; use its "
                ".implicit coordinate spec (see mapping_jax._device_distances)")
        import jax
        key = (id(arr), self.dtype)
        hit = self._device.get(key)
        if hit is not None:
            self._device.move_to_end(key)
            return hit[1]
        self.stats["transfers"] += 1
        with self.scope():
            dev = jax.device_put(np.asarray(arr, dtype=self.np_dtype))
        self._device[key] = (arr, dev)
        while len(self._device) > self._max_cached:
            self._device.popitem(last=False)
        return dev

    def clear_device_cache(self) -> None:
        self._device.clear()


_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def _init_compile_cache(jax) -> None:
    """Point JAX's persistent compilation cache at ``.jax_cache/`` in the
    repository root, unless ``JAX_COMPILATION_CACHE_DIR`` (or code) has
    already chosen a directory.  The path is fixed because it is part of
    the cache key: a directory that moves never hits.  On TPU every
    program is cached, however quick its compile: a service compiles
    dozens of small refine programs, each well under JAX's default
    one-second threshold."""
    if not (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or jax.config.jax_compilation_cache_dir):
        jax.config.update("jax_compilation_cache_dir", str(_CACHE_DIR))
    if jax.default_backend() == "tpu":
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def platform_dtype() -> str:
    """The jax backend's compute dtype on this platform: ``float32`` on
    TPU (no Pallas kernel of the hot path compiles under x64 there),
    ``float64`` elsewhere (bit-identity with the NumPy kernels)."""
    import jax
    return "float32" if jax.default_backend() == "tpu" else "float64"


def has_jax() -> bool:
    """True when the optional jax dependency is importable."""
    try:
        import jax  # noqa: F401
        return True
    except ImportError:
        return False


_NUMPY = NumpyBackend()
_JAX: Optional[JaxBackend] = None


def _resolve_devices(devices: Optional[int]) -> int:
    """Explicit argument, else ``REPRO_JAX_DEVICES``, else 0 (= all)."""
    if devices is not None:
        return int(devices)
    return int(os.environ.get("REPRO_JAX_DEVICES", "0") or 0)


def _jax_backend(dtype: Optional[str] = None,
                 devices: Optional[int] = None) -> JaxBackend:
    global _JAX
    want = dtype or platform_dtype()
    want_dev = _resolve_devices(devices)
    if _JAX is None or _JAX.dtype != want or _JAX.devices != want_dev:
        _JAX = JaxBackend(dtype=want, devices=want_dev)
    return _JAX


def get_backend(name: str, dtype: Optional[str] = None,
                devices: Optional[int] = None):
    """Resolve a backend by name (``numpy`` | ``jax``)."""
    if name == "numpy":
        return _NUMPY
    if name == "jax":
        return _jax_backend(dtype, devices)
    raise ValueError(f"unknown backend {name!r}; have: numpy, jax")


_ACTIVE = get_backend(os.environ.get("REPRO_BACKEND", "numpy"))


def active():
    """The backend the mapping kernels currently dispatch to."""
    return _ACTIVE


def on_accelerator() -> bool:
    """True when the active backend is jax on an accelerator.  Such a
    process holds its chip, and a chip serves one process at a time: a
    worker process started now could not reach it."""
    if not _ACTIVE.is_jax:
        return False
    import jax
    return jax.default_backend() != "cpu"


def set_backend(name: str, dtype: Optional[str] = None,
                devices: Optional[int] = None):
    """Set the process-wide active backend; returns the backend object."""
    global _ACTIVE
    _ACTIVE = get_backend(name, dtype, devices)
    return _ACTIVE


@contextlib.contextmanager
def use(name: str, dtype: Optional[str] = None,
        devices: Optional[int] = None) -> Iterator[object]:
    """Scoped backend switch::

        with backend.use("jax"):
            engine.place(request)        # jitted kernels, device-resident D

    ``devices`` caps the sharded refine dispatch (``devices=1`` pins the
    single-device vmap path; 0/None follows ``REPRO_JAX_DEVICES`` or all
    local devices).
    """
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = get_backend(name, dtype, devices)
    try:
        yield _ACTIVE
    finally:
        _ACTIVE = prev
