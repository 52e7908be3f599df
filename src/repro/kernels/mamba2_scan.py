"""Mamba2 (SSD) chunked scan as a Pallas TPU kernel — zamba2's trunk op.

Same chunked decay-linear-attention structure as rwkv6_scan, specialised to
SSD semantics: scalar-per-head decay (log_w broadcast over the state dim),
decay applied in the output read (y_t reads w_t*S_{t-1} + k_t v_t^T), and
the intra-chunk mask includes the diagonal. Oracle: ref.mamba2_scan_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(r_ref, k_ref, v_ref, lw_ref, y_ref, st_ref, state_s, *,
                chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_s[...] = jnp.zeros_like(state_s)

    r = r_ref[0, 0].astype(jnp.float32)               # (c, N)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)               # (c, hd)
    lw = lw_ref[0, 0].astype(jnp.float32)             # (c, N) broadcasted
    state = state_s[...]                              # (N, hd)

    cl = jnp.cumsum(lw, axis=0)                       # (c, N), <= 0
    e = cl                                            # decay-in-output: cl_t

    r_sc = r * jnp.exp(e)
    y = jax.lax.dot_general(r_sc, state, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # intra-chunk with s <= t (diagonal included, no bonus term)
    expo = jnp.exp(jnp.minimum(e[:, None, :] - cl[None, :, :], 0.0))
    A = jnp.einsum("td,sd,tsd->ts", r, k, expo)
    c = chunk
    tri = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    A = jnp.where(tri, A, 0.0)
    y = y + jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    clc = cl[-1]
    k_sc = k * jnp.exp(clc[None, :] - cl)
    state = jnp.exp(clc)[:, None] * state + jax.lax.dot_general(
        k_sc, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    state_s[...] = state

    @pl.when(ci == n_chunks - 1)
    def _emit():
        st_ref[0, 0] = state


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba2_scan(r, k, v, log_w, *, chunk: int = 64, interpret: bool = True):
    """r/k: (B,S,H,N); v: (B,S,H,hd); log_w: (B,S,H,1) scalar/head decay.

    Returns (y (B,S,H,hd), state (B,H,N,hd) fp32)."""
    B, S, H, N = r.shape
    hd = v.shape[-1]
    c = min(chunk, S)
    n = -(-S // c)
    pad = n * c - S

    def prep(x):
        x = jnp.moveaxis(x, 2, 1)
        return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else x

    rt, kt, vt = prep(r), prep(k), prep(v)
    lwt = prep(jnp.broadcast_to(log_w, r.shape))
    kernel = functools.partial(_ssd_kernel, chunk=c, n_chunks=n)
    y, state = pl.pallas_call(
        kernel,
        grid=(B, H, n),
        in_specs=[
            pl.BlockSpec((1, 1, c, N), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, c, N), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, c, hd), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, c, N), lambda b, h, ci: (b, h, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, c, hd), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, N, hd), lambda b, h, ci: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, n * c, hd), v.dtype),
            jax.ShapeDtypeStruct((B, H, N, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mamba2_scan",
    )(rt, kt, vt, lwt)
    return jnp.moveaxis(y[:, :, :S], 1, 2), state
