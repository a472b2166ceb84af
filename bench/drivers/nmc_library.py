"""The NMC kernels the benchmark's traffic runs, drawn from a seed.

The kernel bodies are the paper's Table V kernels (arXiv:2406.14263,
Table V and its footnotes) written against the ``nmc`` tracer, the way a
user of ``nmc.jit`` writes them.  :func:`instance` draws fresh operands
for one kernel at one element width and returns the traced function, its
operands and what :mod:`bench.ref.nmc` needs to check the output.
"""

from __future__ import annotations

import dataclasses

import numpy as np

DTYPES = {8: np.int8, 16: np.int16, 32: np.int32}
ELEMENTWISE = {"xor": lambda a, b: a ^ b, "add": lambda a, b: a + b,
               "mul": lambda a, b: a * b}


@dataclasses.dataclass
class Instance:
    kind: str
    sew: int
    fn: object             # traced kernel function ``fn(t, *args)``
    args: tuple            # operands (numpy, signed sew-bit integers)
    params: dict           # constants the reference needs


def _rand(rng, shape, sew: int) -> np.ndarray:
    info = np.iinfo(DTYPES[sew])
    return rng.integers(info.min, info.max + 1, shape, dtype=DTYPES[sew])


def elements(shape: dict, sew: int) -> int:
    """Operand length: ``n`` elements, or ``bytes`` of them at ``sew``."""
    return int(shape["n"]) if "n" in shape else int(shape["bytes"]) // (sew // 8)


def instance(kind: str, sew: int, shape: dict, rng) -> Instance:
    """One kernel instance with operands drawn from ``rng``.

    ``shape`` holds the sizes: ``n`` or ``bytes`` (element-wise, relu,
    maxpool), ``m``/``k``/``p`` (matmul, gemm), ``rows``/``n``/``f``
    (conv2d), ``width`` (maxpool), and the constants ``leaky_shift`` and
    ``alpha``/``beta``/``shift``."""
    from repro import nmc

    if kind in ELEMENTWISE:
        op = ELEMENTWISE[kind]
        n = elements(shape, sew)

        def ew(t, x, y):
            t.store(op(t.load(x, bank=0), t.load(y)))
        return Instance(kind, sew, ew, (_rand(rng, n, sew),
                                        _rand(rng, n, sew)), {})
    if kind in ("relu", "leaky_relu"):
        shift = int(shape.get("leaky_shift", 0)) if kind == "leaky_relu" \
            else 0

        def relu(t, x):
            xv = t.load(x)
            t.store(xv.max(0) if shift == 0 else xv.max(xv >> shift))
        return Instance(kind, sew, relu,
                        (_rand(rng, elements(shape, sew), sew),),
                        {"leaky_shift": shift})
    if kind in ("matmul", "gemm"):
        m, k, p = int(shape["m"]), int(shape["k"]), int(shape["p"])
        gemm = kind == "gemm"
        alpha, beta, shift = (int(shape.get(x, 0))
                              for x in ("alpha", "beta", "shift"))

        def matmul(t, A, B, *C0):
            a = t.consts(A)
            rows = [t.load(B[r]) for r in range(k)]
            c0 = [t.load(C0[0][r]) for r in range(m)] if gemm else None
            for i in range(m):
                acc = None
                for kk in range(k):
                    acc = nmc.mac(acc, a[i, kk], rows[kk])
                if gemm:
                    acc = ((acc * alpha) >> shift) + \
                        ((c0[i] * beta) >> shift)
                t.store(acc)
        args = (_rand(rng, (m, k), sew), _rand(rng, (k, p), sew))
        if gemm:
            args += (_rand(rng, (m, p), sew),)
        return Instance(kind, sew, matmul, args,
                        {"alpha": alpha, "beta": beta, "shift": shift})
    if kind == "conv2d":
        rows, n, f = int(shape["rows"]), int(shape["n"]), int(shape["f"])

        def conv2d(t, A, F):
            fw = t.consts(F)
            av = [t.load(A[r]) for r in range(rows)]
            sh = {(dj, r): av[r].slide_down(dj)
                  for dj in range(1, f) for r in range(rows)}
            for i in range(rows - f + 1):
                acc = None
                for di in range(f):
                    for dj in range(f):
                        src = av[i + di] if dj == 0 else sh[(dj, i + di)]
                        acc = nmc.mac(acc, fw[di, dj], src)
                t.store(acc, n=n - f + 1)
        return Instance(kind, sew, conv2d, (_rand(rng, (rows, n), sew),
                                            _rand(rng, (f, f), sew)), {})
    if kind == "maxpool":
        width = int(shape["width"])
        rows = int(shape["rows"]) if "rows" in shape \
            else elements(shape, sew) // width
        X = _rand(rng, (rows, width), sew)
        even = np.ascontiguousarray(X[0::2]).reshape(-1)
        odd = np.ascontiguousarray(X[1::2]).reshape(-1)

        def maxpool(t, e, o):
            t.store(t.load(e, bank=0).max(t.load(o)))
        return Instance(kind, sew, maxpool, (even, odd), {})
    raise KeyError(f"unknown kernel kind {kind!r}")
