"""Mamba-2 SSD chunk kernel: fused intra-chunk attention-like term +
inter-chunk state recurrence for ONE (batch, head) stream.

Grid (batch*heads, n_chunks) with the chunk axis innermost; the SSD state
(P x N) lives in VMEM scratch and carries across chunks — the recurrence
never round-trips HBM, which is the TPU-native restatement of Mamba-2's
"state stays in SRAM" GPU design (DESIGN.md §3).

Per chunk: y = (C B^T ⊙ decay) @ (x dt)  +  C @ state_in ⊙ decay_in;
           state = state * chunk_decay + (B ⊙ decay_to_end dt)^T x.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref, *,
                l: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)        # (l, p)
    dt = dt_ref[0, 0].astype(jnp.float32)      # (l,)
    a = a_ref[0, 0]                            # scalar decay rate (<0)
    bmat = b_ref[0, 0].astype(jnp.float32)     # (l, n)
    cmat = c_ref[0, 0].astype(jnp.float32)     # (l, n)

    da = dt * a
    da_cum = jnp.cumsum(da)                    # (l,)
    seg = da_cum[:, None] - da_cum[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    decay = jnp.where(ii >= jj, jnp.exp(seg), 0.0)

    xdt = x * dt[:, None]
    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y_diag = jax.lax.dot_general(cb * decay, xdt,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    state_in = state_ref[...]                  # (p, n)
    y_off = jax.lax.dot_general(cmat, state_in,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_off = y_off * jnp.exp(da_cum)[:, None]
    y_ref[0, 0] = (y_diag + y_off).astype(y_ref.dtype)

    decay_to_end = jnp.exp(da_cum[-1] - da_cum)
    upd = jax.lax.dot_general(xdt * decay_to_end[:, None], bmat,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    state_ref[...] = state_in * jnp.exp(da_cum[-1]) + upd


def ssd_scan(x, dt, a, bmat, cmat, *, chunk: int = 128,
             interpret: bool | None = None):
    """x (B,S,H,P); dt (B,S,H) >=0; a (H,) <0; b/c (B,S,N) shared across
    heads (n_groups=1). Returns y (B,S,H,P) float32 (pre-gating)."""
    b, s, h, p = x.shape
    n = bmat.shape[-1]
    l = min(chunk, s)
    assert s % l == 0
    nc = s // l
    # (B*H, nc, l, ...) streams
    xs = x.transpose(0, 2, 1, 3).reshape(b * h, nc, l, p)
    dts = dt.transpose(0, 2, 1).reshape(b * h, nc, l)
    a_s = jnp.tile(a, b).reshape(b * h, 1)
    bs = jnp.broadcast_to(bmat[:, None], (b, h, s, n)).reshape(
        b * h, nc, l, n)
    cs = jnp.broadcast_to(cmat[:, None], (b, h, s, n)).reshape(
        b * h, nc, l, n)
    from jax.experimental.pallas import tpu as pltpu
    y = pl.pallas_call(
        functools.partial(_ssd_kernel, l=l),
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, l, p), lambda g, c: (g, c, 0, 0)),
            pl.BlockSpec((1, 1, l), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, 1), lambda g, c: (g, 0)),
            pl.BlockSpec((1, 1, l, n), lambda g, c: (g, c, 0, 0)),
            pl.BlockSpec((1, 1, l, n), lambda g, c: (g, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, l, p), lambda g, c: (g, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, nc, l, p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(xs, dts, a_s, bs, cs)
    return y.reshape(b, h, s, p).transpose(0, 2, 1, 3)
