"""RWKV6 (Finch) chunked WKV recurrence as a Pallas TPU kernel.

Grid (B, H, n_chunks): chunks are sequential; the (Dk, Dv) state matrix
persists in VMEM scratch across chunk steps. All exponentials are of
non-positive numbers (decay ratios between ordered positions), so the chunk
math is fp32-safe without secondary chunking — same algorithm as
``models.linear_scan.chunked_decay_attention`` (the jnp path the dry-run
lowers), validated against the naive-scan oracle in ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, y_ref, st_ref, state_s,
                 *, chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_s[...] = jnp.zeros_like(state_s)

    r = r_ref[0, 0].astype(jnp.float32)               # (c, Dk)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)               # (c, Dv)
    lw = lw_ref[0, 0].astype(jnp.float32)             # (c, Dk), <= 0
    u = u_ref[0].astype(jnp.float32)                  # (Dk,)
    state = state_s[...]                              # (Dk, Dv)

    cl = jnp.cumsum(lw, axis=0)                       # (c, Dk)
    e = cl - lw                                       # cl_{t-1}

    # inter-chunk: read state with decay exp(e_t)
    r_sc = r * jnp.exp(e)
    y = jax.lax.dot_general(r_sc, state, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # intra-chunk: A[t,s] = sum_d r_t k_s exp(e_t - cl_s) (s < t), u on diag
    expo = jnp.exp(e[:, None, :] - cl[None, :, :])    # (t, s, Dk) args <= 0
    A = jnp.einsum("td,sd,tsd->ts", r, k, expo)
    c = chunk
    tri = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0) \
        > jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    A = jnp.where(tri, A, 0.0)
    diag = ((r * u) * k).sum(axis=1)                  # (c,)
    A = A + diag[:, None] * jnp.eye(c, dtype=jnp.float32)
    y = y + jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: S' = diag(exp(cl_c)) S + sum_s exp(cl_c - cl_s) k_s v_s^T
    clc = cl[-1]                                      # (Dk,)
    k_sc = k * jnp.exp(clc[None, :] - cl)
    state = jnp.exp(clc)[:, None] * state + jax.lax.dot_general(
        k_sc, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    state_s[...] = state

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        st_ref[0, 0] = state


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(r, k, v, log_w, u, *, chunk: int = 64, interpret: bool = True):
    """r/k/log_w: (B,S,H,Dk); v: (B,S,H,Dv); u: (H,Dk).

    Returns (y (B,S,H,Dv), state (B,H,Dk,Dv) fp32)."""
    B, S, H, Dk = r.shape
    Dv = v.shape[-1]
    c = min(chunk, S)
    n = -(-S // c)
    pad = n * c - S

    def prep(x):
        x = jnp.moveaxis(x, 2, 1)                     # (B,H,S,·)
        return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else x

    rt, kt, vt = prep(r), prep(k), prep(v)
    lwt = prep(log_w)  # padded zeros decay = exp(0)=1: harmless, masked below
    kernel = functools.partial(_rwkv_kernel, chunk=c, n_chunks=n)
    y, state = pl.pallas_call(
        kernel,
        grid=(B, H, n),
        in_specs=[
            pl.BlockSpec((1, 1, c, Dk), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, c, Dk), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, c, Dv), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, c, Dk), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, Dk), lambda b, h, ci: (h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, c, Dv), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, Dk, Dv), lambda b, h, ci: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, n * c, Dv), v.dtype),
            jax.ShapeDtypeStruct((B, H, Dk, Dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((Dk, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="rwkv6_scan",
    )(rt, kt, vt, lwt, u)
    return jnp.moveaxis(y[:, :, :S], 1, 2), state
