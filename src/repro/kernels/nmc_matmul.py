"""Pallas TPU kernel: W8A8 integer matmul with fused near-memory epilogue.

This is the NM-Carus ``vmacc`` loop mapped onto the MXU (DESIGN.md Layer B):

* int8 x int8 -> int32 accumulation (the paper's rule: MACs accumulate at
  32-bit regardless of operand width — Section III-A2 / III-B2);
* the accumulator lives in VMEM scratch across the whole K reduction — the
  "compute at the register file" pattern: partial sums never round-trip HBM;
* the dequant + bias + activation epilogue is fused: the result leaves VMEM
  exactly once, already in its final form (the NMC "results are directly
  accessible, eliminating additional data movement" contract).

Any (M, K) x (K, N) runs.  ``bk`` / ``bn`` are the largest multiples of
128 up to the requested tile that divide K / N (qwen1.5-0.5b: K = 2816 =
11 * 256 takes bk = 256, N = 151936 = 1187 * 128 takes bn = 128); a K or N
that is no multiple of 128 zero-pads to one (zero rows add nothing to the
int32 sums).  M zero-pads to a multiple of ``bm``, itself a multiple of 32
(int8 sublane packing) — decode at M = n_slots and prefill at any prompt
length share the kernel.  Scale and bias ride as ``(1, N)`` rows in
``(1, bn)`` blocks (a 1-D block's layout is refused by Mosaic).

VMEM footprint per grid step:
  bm*bk + bk*bn (int8)  +  bm*bn*4 (int32 acc)  +  bm*bn*out_bytes
e.g. the default 256/256/512 tiles use 256*512*2 + 256*256*4 + ... ~ 0.6 MiB,
far under a v5e's 16 MiB scoped VMEM, allowing the pipeline to double-buffer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref


def _kernel(x_ref, w_ref, scale_ref, bias_ref, o_ref, acc_ref, *,
            nk: int, act: str):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _epilogue():
        y = acc_ref[...].astype(jnp.float32) * scale_ref[...]
        y = y + bias_ref[...]
        o_ref[...] = ref.apply_act(y, act).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("act", "out_dtype", "bm", "bn",
                                             "bk", "interpret"))
def nmc_matmul(x_q: jax.Array, w_q: jax.Array, scale: jax.Array,
               bias: jax.Array | None = None, *, act: str = "none",
               out_dtype=jnp.float32, bm: int = 256, bn: int = 256,
               bk: int = 512, interpret: bool = False) -> jax.Array:
    """y[M,N] = act((x_q[M,K] @ w_q[K,N]) * scale[N] + bias[N])."""
    m, k = x_q.shape
    k2, n = w_q.shape
    assert k == k2, (x_q.shape, w_q.shape)
    bk, kp = _lane_tile(k, bk)
    bn, np_ = _lane_tile(n, bn)
    bm = min(_round_up(bm, 32), _round_up(m, 32))
    mp = _round_up(m, bm)
    if bias is None:
        bias = jnp.zeros((n,), jnp.float32)
    x_q = jnp.pad(x_q, ((0, mp - m), (0, kp - k)))
    w_q = jnp.pad(w_q, ((0, kp - k), (0, np_ - n)))
    scale = jnp.pad(scale.astype(jnp.float32), (0, np_ - n)).reshape(1, np_)
    bias = jnp.pad(bias.astype(jnp.float32), (0, np_ - n)).reshape(1, np_)
    nk = kp // bk
    y = pl.pallas_call(
        functools.partial(_kernel, nk=nk, act=act),
        grid=(mp // bm, np_ // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x_q, w_q, scale, bias)
    return y[:m, :n]


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _lane_tile(dim: int, pref: int) -> tuple[int, int]:
    """(tile, padded dim): the largest multiple of 128 up to ``pref`` that
    divides ``dim`` rounded up to a multiple of 128."""
    padded = _round_up(dim, 128)
    tile = max(128, pref // 128 * 128)
    while padded % tile:
        tile -= 128
    return tile, padded
