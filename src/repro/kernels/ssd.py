"""Pallas TPU Mamba2 SSD kernel: chunked state-space scan.

Grid = (B, H, n_chunks) with the chunk index sequential; (N, P) state in
VMEM scratch. Per chunk: the intra-chunk (Q, Q) decay-weighted C.B matmul
runs on the MXU; decays are scalar per head so the tile is 2-D (unlike
wkv6's per-channel 3-D decay). All exponents <= 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mosaic import cumsum_row, dot, row_to_col


def _kernel(x_ref, dl_ref, b_ref, c_ref, y_ref, h_scr, *, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0].astype(jnp.float32)       # (Q, P)
    dl = dl_ref[0, 0]                          # (2, Q) f32
    dt = dl[0:1, :]                            # (1, Q)
    la = dl[1:2, :]                            # (1, Q) log decay dt * A
    Bm = b_ref[0, 0].astype(jnp.float32)      # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)      # (Q, N)
    h = h_scr[...]                             # (N, P)

    cum = cumsum_row(la)                       # (1, Q)
    cum_c = row_to_col(cum)                    # (Q, 1)
    Q = x.shape[0]
    cb = dot(Cm, Bm, ((1,), (1,)))             # (Q, Q)
    dec = jnp.exp(jnp.minimum(cum_c - cum, 0.0))
    tri = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    M = jnp.where(tri, cb * dec, 0.0) * dt
    y = dot(M, x) + jnp.exp(cum_c) * dot(Cm, h)
    last = jnp.sum(la, axis=1, keepdims=True)  # (1, 1) == cum at Q - 1
    w = row_to_col(jnp.exp(last - cum) * dt)   # (Q, 1)
    # (1, 1) -> (1, P) -> (N, P): Mosaic broadcasts one axis at a time
    decay = jnp.broadcast_to(jnp.exp(last), (1, h.shape[1]))
    h_scr[...] = h * decay + dot(Bm * w, x, ((0,), (0,)))
    y_ref[0, 0] = y.astype(y_ref.dtype)


def ssd(xs, dt, A, Bm, Cm, *, chunk: int = 128, interpret: bool = False):
    """xs: (B,S,H,P); dt: (B,S,H) f32; A: (H,); Bm/Cm: (B,S,H,N).
    Returns (y (B,S,H,P) f32, None)."""
    B, S, H, P = xs.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    pad = (-S) % chunk

    def prep(a):
        a = jnp.moveaxis(a, 2, 1)
        if pad:
            cfg = [(0, 0)] * a.ndim
            cfg[2] = (0, pad)
            a = jnp.pad(a, cfg)
        return a

    xt = prep(xs)
    bt = prep(Bm)
    ct = prep(Cm)
    # dt and the log decay dt * A as two rows per head, (B, H, 2, S): the
    # per-head scalar A never enters the kernel, and a (2, chunk) block
    # meets the TPU tiling rule where a (1, chunk) block of (B, H, S) not
    dl = jnp.stack([dt, dt * A[None, None, :]], axis=1)   # (B, 2, S, H)
    dl = jnp.moveaxis(dl, 3, 1)                           # (B, H, 2, S)
    if pad:
        dl = jnp.pad(dl, ((0, 0), (0, 0), (0, 0), (0, pad)))
    n_chunks = (S + pad) // chunk

    kernel = functools.partial(_kernel, n_chunks=n_chunks)
    y = pl.pallas_call(
        kernel,
        grid=(B, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 2, chunk), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, h, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, n_chunks * chunk, P),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(xt, dl, bt, ct)
    return jnp.moveaxis(y, 1, 2)[:, :S], None
