"""Plain numpy references of the NMC kernels the benchmark runs.

Each takes the kernel's operands (signed integers of width ``sew``) and
returns its outputs with the tile's arithmetic: two's complement, every
result wrapped to ``sew`` bits, shifts arithmetic.  Nothing here imports
the program.

``saturate=True`` is the control: the same arithmetic with results
clipped to the ``sew`` range instead of wrapped, which breaks the
wrap-around guarantee that ``bench/configs/nmc-edge.json`` states.
"""

from __future__ import annotations

import numpy as np


def wrap(x, sew: int, saturate: bool = False) -> np.ndarray:
    """Integers -> the signed ``sew``-bit value the tile holds."""
    x = np.asarray(x, np.int64)
    half = 1 << (sew - 1)
    if saturate:
        return np.clip(x, -half, half - 1)
    return ((x + half) % (1 << sew)) - half


def _i(a) -> np.ndarray:
    return np.asarray(a, np.int64)


def elementwise(op: str, a, b, sew: int, saturate: bool = False):
    a, b = _i(a), _i(b)
    raw = {"xor": lambda: a ^ b, "add": lambda: a + b,
           "mul": lambda: a * b}[op]()
    return wrap(raw, sew, saturate)


def relu(x, sew: int, leaky_shift: int = 0, saturate: bool = False):
    x = _i(x)
    other = 0 if leaky_shift == 0 else x >> leaky_shift
    return wrap(np.maximum(x, other), sew, saturate)


def matmul(A, B, sew: int, saturate: bool = False):
    """``C = A @ B``, accumulated tap by tap (each step wraps)."""
    A, B = _i(A), _i(B)
    acc = wrap(A[:, :1] * B[:1], sew, saturate)
    for k in range(1, A.shape[1]):
        acc = wrap(acc + wrap(A[:, k:k + 1] * B[k:k + 1], sew, saturate),
                   sew, saturate)
    return acc


def gemm(A, B, C0, sew: int, alpha: int, beta: int, shift: int,
         saturate: bool = False):
    """``(alpha * (A @ B) >> shift) + (beta * C0 >> shift)``."""
    acc = matmul(A, B, sew, saturate)
    t1 = wrap(acc * alpha, sew, saturate) >> shift
    t2 = wrap(_i(C0) * beta, sew, saturate) >> shift
    return wrap(t1 + t2, sew, saturate)


def conv2d(A, F, sew: int, saturate: bool = False):
    """'valid' 2-D correlation of ``A`` with the filter ``F``."""
    A, F = _i(A), _i(F)
    rows, n = A.shape
    f = F.shape[0]
    out = np.zeros((rows - f + 1, n - f + 1), np.int64)
    first = True
    for di in range(f):
        for dj in range(f):
            term = wrap(F[di, dj] * A[di:di + rows - f + 1, dj:dj + n - f + 1],
                        sew, saturate)
            out = term if first else wrap(out + term, sew, saturate)
            first = False
    return out


def maxpool_vertical(even, odd, sew: int, saturate: bool = False):
    """The tile's stage of 2x2 max pooling: the max of row pairs."""
    return wrap(np.maximum(_i(even), _i(odd)), sew, saturate)


def reference(kind: str, args: tuple, sew: int, params: dict,
              saturate: bool = False) -> np.ndarray:
    """Reference output of one kernel instance, flattened."""
    if kind in ("xor", "add", "mul"):
        out = elementwise(kind, *args, sew, saturate)
    elif kind == "relu":
        out = relu(*args, sew, 0, saturate)
    elif kind == "leaky_relu":
        out = relu(*args, sew, params["leaky_shift"], saturate)
    elif kind == "matmul":
        out = matmul(args[0], args[1], sew, saturate)
    elif kind == "gemm":
        out = gemm(*args, sew, params["alpha"], params["beta"],
                   params["shift"], saturate)
    elif kind == "conv2d":
        out = conv2d(*args, sew, saturate)
    elif kind == "maxpool":
        out = maxpool_vertical(*args, sew, saturate)
    else:
        raise KeyError(kind)
    return np.asarray(out).reshape(-1)


def mismatches(got, want) -> int:
    """Elements that differ (a size mismatch counts every element)."""
    got = np.asarray(got, np.int64).reshape(-1)
    want = np.asarray(want, np.int64).reshape(-1)
    if got.size != want.size:
        return max(got.size, want.size)
    return int(np.count_nonzero(got != want))
