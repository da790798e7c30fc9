"""jit'd public wrappers for the Pallas kernels.

On a TPU backend the kernels compile to Mosaic; elsewhere they execute in
interpret mode (the kernel body runs as plain JAX ops — what the CPU
tests check against the oracles). The transform kernels take images as
(H, W*3) row slabs, several per grid step (kernels/image_transform.py);
tests/test_tpu_compile.py compiles them for a v5e chip.
``backend='ref'`` forces the pure-jnp oracle (the dry-run path, so XLA
cost analysis sees the FLOPs — DESIGN.md §3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import flash_attention as _fa
from repro.kernels import image_transform as _it
from repro.kernels import matmul as _mm
from repro.kernels import ref as _ref
from repro.kernels import ssd_scan as _ssd

COLOR_WEIGHTS = {
    "rgb": np.eye(3, dtype=np.float32),
    "r": np.array([[1.0], [0.0], [0.0]], np.float32),
    "g": np.array([[0.0], [1.0], [0.0]], np.float32),
    "b": np.array([[0.0], [0.0], [1.0]], np.float32),
    "gray": np.array([[0.299], [0.587], [0.114]], np.float32),
}


def _interpret() -> bool:
    from repro.kernels import resolve_interpret
    return resolve_interpret(None)


@functools.partial(jax.jit, static_argnames=("res", "color", "backend"))
def transform_op(images, *, res: int, color: str = "rgb",
                 backend: str = "pallas"):
    cw = jnp.asarray(COLOR_WEIGHTS[color])
    if backend == "ref":
        return _ref.fused_transform_ref(images, cw, res)
    return _it.fused_transform(images, cw, res, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("specs", "backend"))
def pyramid_transform_op(images, *, specs, backend: str = "pallas"):
    """Multi-output fused transform. specs: tuple of (res, color) pairs —
    one output tensor per pair, all from a single pass over the base
    image (kernels/image_transform.fused_pyramid_transform)."""
    rep_specs = [(res, jnp.asarray(COLOR_WEIGHTS[color]))
                 for res, color in specs]
    if backend == "ref":
        return _ref.fused_pyramid_transform_ref(images, rep_specs)
    return _it.fused_pyramid_transform(images, rep_specs,
                                       interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("backend",))
def matmul_op(a, b, *, backend: str = "pallas"):
    if backend == "ref":
        return _ref.matmul_ref(a, b)
    return _mm.matmul(a, b, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("causal", "backend"))
def flash_attention_op(q, k, v, *, causal: bool = True,
                       backend: str = "pallas"):
    if backend == "ref":
        return _ref.flash_attention_ref(q, k, v, causal=causal)
    return _fa.flash_attention(q, k, v, causal=causal,
                               interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk", "backend"))
def ssd_scan_op(x, dt, a, bmat, cmat, *, chunk: int = 128,
                backend: str = "pallas"):
    if backend == "ref":
        return _ref.ssd_scan_ref(x, dt, a, bmat, cmat, chunk=chunk)
    return _ssd.ssd_scan(x, dt, a, bmat, cmat, chunk=chunk,
                         interpret=_interpret())
