"""RG-LRU linear-recurrence kernel for TPU.

Hardware adaptation (DESIGN.md): GPU implementations of gated linear
recurrences lean on warp-level parallel scans; the TPU-native formulation
keeps the recurrence *sequential in time* but resident in VMEM — the state
(block_w,) vector never touches HBM between steps, and the time axis is
streamed through VMEM in (block_t, block_w) tiles.  Grid:
(B, W/block_w, T/block_t), with the last dim iterating sequentially so the
carry lives in VMEM scratch.

Inputs: decay a, gated input gx (B, T, W) fp32, initial state h0 (B, W).
Output: h (B, T, W) with h_t = a_t * h_{t-1} + gx_t.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_T = 256
DEFAULT_BLOCK_W = 128


def _rglru_kernel(a_ref, gx_ref, h0_ref, o_ref, carry, *, block_t: int):
    jt = pl.program_id(2)

    @pl.when(jt == 0)
    def _init():
        carry[...] = h0_ref[0]

    a = a_ref[0]                       # (block_t, block_w)
    gx = gx_ref[0]

    # sequential in time, state in VMEM
    def body(t, h):
        h = a[t] * h + gx[t]
        o_ref[pl.ds(0, 1), pl.ds(t, 1), :] = h[None, None]
        return h

    h = jax.lax.fori_loop(0, block_t, body, carry[...])
    carry[...] = h


def rglru_scan(a: jnp.ndarray, gx: jnp.ndarray, h0: jnp.ndarray, *,
               block_t: int = DEFAULT_BLOCK_T,
               block_w: int = DEFAULT_BLOCK_W,
               interpret: bool = False) -> jnp.ndarray:
    """a, gx: (B, T, W) fp32; h0: (B, W) -> h (B, T, W)."""
    b, t, w = a.shape
    block_t = min(block_t, t)
    block_w = min(block_w, w)
    assert t % block_t == 0 and w % block_w == 0, (t, w, block_t, block_w)
    grid = (b, w // block_w, t // block_t)
    io_spec = pl.BlockSpec((1, block_t, block_w),
                           lambda bb, jw, jt: (bb, jt, jw))
    h0_spec = pl.BlockSpec((1, block_w), lambda bb, jw, jt: (bb, jw))
    out = pl.pallas_call(
        functools.partial(_rglru_kernel, block_t=block_t),
        grid=grid,
        in_specs=[io_spec, io_spec, h0_spec],
        out_specs=io_spec,
        out_shape=jax.ShapeDtypeStruct((b, t, w), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_w,), jnp.float32)],
        interpret=interpret,
    )(a.astype(jnp.float32), gx.astype(jnp.float32), h0.astype(jnp.float32))
    return out
