"""hop_dist — implicit hop distances, computed from coordinates.

The implicit-distance contract of the mapping pipeline: instead of
gathering ``D[u, v]`` from a stored O(N^2) matrix, compute the metric
directly from the (N, ndim) coordinate table — O(N) memory for any
topology size.  Two metrics live here:

    torus:    hop(u, v) = sum_d min(|cu_d - cv_d|, dim_d - |cu_d - cv_d|)
              (+ 100 * badlinks(u, v) on a faulty torus, given the (ndim, F)
              table of penalised coordinates: Eq. (1), see :mod:`.ref`)
    fat-tree: hop(u, v) = 0 | 2 | 4 | 6  (same host / edge / pod / across)

Three implementations share this module's dispatch:

* :func:`torus_hop_np` / :func:`torus_hop_pairs_np` — pure NumPy, no jax
  import at module scope, so :class:`repro.core.lazydist.LazyDistance`
  works on NumPy-only installs.
* :mod:`.ref` — jitted ``jnp`` reference (CPU/GPU, and the differential
  oracle for the kernel).
* :mod:`.kernel` — Pallas TPU kernel tiling the coordinate table through
  VMEM row blocks.

``impl="auto"`` runs the Pallas kernel on TPU and the jitted reference
everywhere else — the same fallback contract as
:mod:`repro.kernels.swap_gain`.
"""
from __future__ import annotations

import functools

import numpy as np


# ------------------------------------------------------------- numpy fallback

def torus_hop_np(cu, cv, dims) -> np.ndarray:
    """Elementwise hop distance; broadcastable ``(..., ndim)`` coords in,
    float64 ``(...)`` out.  Pure NumPy — never imports jax."""
    cu = np.asarray(cu, dtype=np.int64)
    cv = np.asarray(cv, dtype=np.int64)
    out = None
    for k, d in enumerate(dims):
        diff = np.abs(cu[..., k] - cv[..., k])
        h = np.minimum(diff, d - diff)
        out = h if out is None else out + h
    return np.asarray(out, dtype=np.float64)


def torus_hop_pairs_np(cu, cv, dims) -> np.ndarray:
    """All-pairs form: (m, ndim), (k, ndim) -> (m, k) float64."""
    cu = np.asarray(cu)
    cv = np.asarray(cv)
    return torus_hop_np(cu[:, None, :], cv[None, :, :], dims)


def fattree_hop_np(cu, cv) -> np.ndarray:
    """Elementwise fat-tree hop count from broadcastable (..., 3)
    (pod, edge, host) coordinate triples: 0 same host, 2 same edge
    switch, 4 same pod, 6 across pods.  Pure NumPy — never imports jax
    (:class:`repro.core.lazydist.FatTreeLazyDistance` routes through
    here on NumPy-only installs)."""
    cu = np.asarray(cu, dtype=np.int64)
    cv = np.asarray(cv, dtype=np.int64)
    same_pod = cu[..., 0] == cv[..., 0]
    same_edge = same_pod & (cu[..., 1] == cv[..., 1])
    same_host = same_edge & (cu[..., 2] == cv[..., 2])
    return 6.0 - 2.0 * same_pod - 2.0 * same_edge - 2.0 * same_host


def fattree_hop_pairs_np(cu, cv) -> np.ndarray:
    """All-pairs form: (m, 3), (k, 3) -> (m, k) float64."""
    cu = np.asarray(cu)
    cv = np.asarray(cv)
    return fattree_hop_np(cu[:, None, :], cv[None, :, :])


# --------------------------------------------------------------- jax dispatch

def _resolve(impl: str) -> str:
    if impl == "auto":
        import jax
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return impl


def torus_hop_pairs(cu, cv, dims, pen=None, impl: str = "auto"):
    """Traceable all-pairs hop distance: (m, ndim), (k, ndim) -> (m, k).

    Safe to call inside other jitted code (the jitted refine loop of
    :mod:`repro.core.mapping_jax` builds its gathered-distance matrix
    through here).  ``pen``, an (ndim, F) table of penalised coordinates
    padded with -1, adds Eq. (1)'s fault term; F = 0 is the healthy
    metric.
    """
    impl = _resolve(impl)
    if pen is not None and pen.shape[1] == 0:
        pen = None
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels.hop_dist.kernel import torus_hop_tpu
        return torus_hop_tpu(cu, cv, dims, pen,
                             interpret=(impl == "pallas_interpret"))
    from repro.kernels.hop_dist.ref import torus_hop_pairs_ref
    return torus_hop_pairs_ref(cu, cv, dims, pen)


def fattree_hop_pairs(cu, cv, impl: str = "auto"):
    """Traceable all-pairs fat-tree hop count: (m, 3), (k, 3) -> (m, k).

    Same contract as :func:`torus_hop_pairs` — safe inside other jitted
    code (the fat-tree implicit branch of
    :func:`repro.core.mapping_jax._dist_fns` builds its gathered-distance
    matrix through here).
    """
    impl = _resolve(impl)
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels.hop_dist.kernel import fattree_hop_tpu
        return fattree_hop_tpu(cu, cv,
                               interpret=(impl == "pallas_interpret"))
    from repro.kernels.hop_dist.ref import fattree_hop_pairs_ref
    return fattree_hop_pairs_ref(cu, cv)


@functools.lru_cache(maxsize=64)
def _jitted(dims: tuple | None, impl: str):
    import jax

    def f(cu, cv):
        if dims is None:
            return fattree_hop_pairs(cu, cv, impl=impl)
        return torus_hop_pairs(cu, cv, dims, impl=impl)
    return jax.jit(f)


def torus_hop(cu, cv, dims, *, impl: str = "auto"):
    """Jitted public entry: (m, ndim), (k, ndim) device/host arrays ->
    (m, k) hop distances on the active jax device."""
    return _jitted(tuple(int(d) for d in dims), _resolve(impl))(cu, cv)


def fattree_hop(cu, cv, *, impl: str = "auto"):
    """Jitted public entry, fat-tree metric: (m, 3), (k, 3) -> (m, k)."""
    return _jitted(None, _resolve(impl))(cu, cv)
