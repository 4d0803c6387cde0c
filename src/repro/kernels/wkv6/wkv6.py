"""Chunked WKV6 Pallas TPU kernel.

Grid (batch, head, time_chunks) with the chunk axis innermost; the
recurrent state lives in VMEM scratch across the chunk sweep (transposed,
[Dv, D]), starting from the initial state ``s0``.  Within a chunk the
intra-chunk attention uses the pairwise decay
exp(cumlogw[t-1] - cumlogw[s]) whose exponents are all <= 0, so the kernel
is stable for arbitrarily strong data-dependent decay (the
factored r*exp(cw) / k*exp(-cw) form would overflow); it is built one key
position at a time, C*D floats live per step, plus the state tile.

The kernel reads head-major ``[B, H, T, D]`` tiles: the TPU compiler tiles
the last two block dimensions by (8, 128) unless they span the whole array,
so the head axis cannot be one of them.  The wrapper transposes the
``[B, T, H, D]`` inputs and the output, one copy of each per call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, o_ref, sf_ref,
                 s_ref, *, chunk: int, nc: int):
    """The state is carried transposed, ``S^T [Dv, D]``, so the per-key decay
    scales it along its last (lane) axis and no in-kernel transpose or
    row-to-column reshape is needed."""
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0]

    r = r_ref[0, 0, :, :].astype(jnp.float32)       # [C, D]
    k = k_ref[0, 0, :, :].astype(jnp.float32)
    v = v_ref[0, 0, :, :].astype(jnp.float32)       # [C, Dv]
    lw = lw_ref[0, 0, :, :].astype(jnp.float32)
    u = u_ref[0]                                     # [1, D]

    # inclusive prefix sum over time as a lower-triangular matmul (Mosaic
    # has no cumsum lowering)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
           ).astype(jnp.float32)
    cw = jax.lax.dot_general(tri, lw, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [C, D]
    cwx = cw - lw                                    # exclusive
    st = s_ref[...]                                  # [Dv, D]

    # inter-chunk contribution
    rq = r * jnp.exp(cwx)
    out = jax.lax.dot_general(rq, st, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)   # [C, Dv]

    # intra-chunk: A[t, s'] = sum_i r[t,i] k[s',i] exp(cwx[t,i] - cw[s',i]),
    # one key position s' at a time so every array stays two-dimensional
    # (Mosaic cannot lay out the [C, C, D] pairwise tensor)
    t_col = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    for sp in range(chunk):
        dec = jnp.exp(jnp.minimum(cwx - cw[sp:sp + 1, :], 0.0))     # [C, D]
        col = jnp.sum(r * k[sp:sp + 1, :] * dec, axis=-1, keepdims=True)
        out += jnp.where(t_col > sp, col, 0.0) * v[sp:sp + 1, :]

    # current-token bonus
    diag = jnp.sum(r * u * k, axis=-1, keepdims=True)               # [C, 1]
    out += diag * v
    o_ref[0, 0, :, :] = out.astype(o_ref.dtype)

    # state update: S^T <- S^T * exp(cw_last) + v^T (k * exp(cw_last - cw))
    cw_last = cw[chunk - 1:chunk, :]                                 # [1, D]
    k_dec = k * jnp.exp(cw_last - cw)                                # [C, D]
    s_ref[...] = st * jnp.exp(cw_last) + jax.lax.dot_general(
        v, k_dec, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ci == nc - 1)
    def _emit_state():
        sf_ref[0, 0] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_pallas(
    r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, w: jnp.ndarray,
    u: jnp.ndarray, s0: jnp.ndarray | None = None, *, chunk: int = 32,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    b, t, h, d = r.shape
    dv = v.shape[-1]
    s0t = jnp.zeros((b, h, dv, d), jnp.float32) if s0 is None \
        else jnp.swapaxes(s0.astype(jnp.float32), 2, 3)
    lw = jnp.log(jnp.clip(w.astype(jnp.float32), 1e-12, 1.0))
    c = min(chunk, t)
    t_p = -(-t // c) * c
    if t_p != t:
        pad = ((0, 0), (0, t_p - t), (0, 0), (0, 0))
        r, k, v = (jnp.pad(x, pad) for x in (r, k, v))
        lw = jnp.pad(lw, pad)
    nc = t_p // c
    r, k, v, lw = (jnp.swapaxes(x, 1, 2) for x in (r, k, v, lw))  # [B,H,T,D]

    kernel = functools.partial(_wkv6_kernel, chunk=c, nc=nc)
    out, s_fin = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, c, d), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, c, d), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, c, dv), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, c, d), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, d), lambda bi, hi, ci: (hi, 0, 0)),
            pl.BlockSpec((1, 1, dv, d), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, c, dv), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, dv, d), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t_p, dv), r.dtype),
            jax.ShapeDtypeStruct((b, h, dv, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dv, d), jnp.float32)],
        interpret=interpret,
    )(r, k, v, lw, u.astype(jnp.float32).reshape(h, 1, d), s0t)
    return jnp.swapaxes(out, 1, 2)[:, :t], jnp.swapaxes(s_fin, 2, 3)
