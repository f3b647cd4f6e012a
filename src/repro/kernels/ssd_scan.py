"""Mamba2 SSD chunked scan as a Pallas TPU kernel.

TPU adaptation of the Mamba2 Triton kernel (DESIGN §3): the GPU version
splits intra-chunk / state-passing / inter-chunk into three kernels tied by
global memory; on TPU we fuse all three into ONE kernel whose grid walks
(batch, head, chunk) with the chunk axis innermost and sequential — the
running state h (P x N, fp32) lives in VMEM scratch and is carried across
chunk iterations, so inter-chunk state never round-trips through HBM.

Per chunk (Q = chunk length):
    cum    = cumsum(dt * a)                    (Q,)
    y_intra[i] = sum_{j<=i} exp(cum_i-cum_j) * dt_j * (C_i.B_j) * x_j
    y_inter[i] = exp(cum_i) * C_i . h_in
    h_out  = exp(cum_{Q-1}) * h_in + sum_j exp(cum_{Q-1}-cum_j) dt_j B_j x_j^T
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, h_ref, *,
            chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)                      # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)                    # (Q,)
    a = a_ref[0].astype(jnp.float32)                         # ()
    B_ = b_ref[0, 0].astype(jnp.float32)                     # (Q, N)
    C_ = c_ref[0, 0].astype(jnp.float32)                     # (Q, N)
    Q = chunk

    delta = dt * a                                           # (Q,) <= 0
    cum = jnp.cumsum(delta)                                  # inclusive

    # ---- intra-chunk (quadratic within chunk)
    seg = cum[:, None] - cum[None, :]                        # (Q, Q)
    rows = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(rows >= cols, jnp.exp(seg), 0.0)
    CB = jax.lax.dot_general(C_, B_, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    M = CB * L * dt[None, :]
    y = jax.lax.dot(M, x, preferred_element_type=jnp.float32)     # (Q, P)

    # ---- inter-chunk: contribution of the state entering this chunk
    h_in = h_ref[...]                                        # (P, N)
    y = y + jnp.exp(cum)[:, None] * jax.lax.dot_general(
        C_, h_in, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                  # (Q, P)

    # ---- state update for the next chunk
    w_end = jnp.exp(cum[-1] - cum) * dt                      # (Q,)
    newstate = jax.lax.dot_general(
        x, w_end[:, None] * B_, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                  # (P, N)
    h_ref[...] = h_in * jnp.exp(cum[-1]) + newstate

    y_ref[0, 0] = y.astype(y_ref.dtype)


def ssd_scan(x, dt, a, B_, C_, *, chunk: int = 128,
             interpret: bool = False) -> jax.Array:
    """x: (B,H,S,P) f32; dt: (B,H,S) f32 (post-softplus); a: (H,) f32 (<0);
    B_/C_: (B,G,S,N) f32, groups broadcast over H//G heads. S % chunk == 0.
    Returns y: (B,H,S,P) f32 (zero initial state — matches ssd_scan_ref)."""
    Bb, H, S, P = x.shape
    G, N = B_.shape[1], B_.shape[3]
    assert S % chunk == 0, (S, chunk)
    assert H % G == 0
    hpg = H // G
    nc = S // chunk

    kernel = functools.partial(_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(Bb, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, h // hpg, c, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, h // hpg, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((Bb, H, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, a, B_, C_)
