"""Operations and bytes of the LM kernels and the served model, from shapes.

Counts are what the algorithm needs for the call, not what a kernel
happens to compute: padding rows or blocks a kernel adds are its own
inefficiency and show as a lower roofline share.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool


def dims(cfg: dict) -> Dims:
    """Model sizes from a configuration file in Hugging Face's keys."""
    h = int(cfg["num_attention_heads"])
    d = int(cfg["hidden_size"])
    return Dims(layers=int(cfg["num_hidden_layers"]), d_model=d, heads=h,
                kv_heads=int(cfg["num_key_value_heads"]),
                head_dim=int(cfg.get("head_dim", d // h)),
                d_ff=int(cfg["intermediate_size"]),
                vocab=int(cfg["vocab_size"]),
                qkv_bias=bool(cfg.get("qkv_bias", True)))


def nmc_matmul(m: int, k: int, n: int, bias: bool, out_bytes: int = 2
               ) -> tuple[int, int]:
    """W8A8 GEMM ``(m, k) x (k, n)``: int8 operands, f32 scale and bias
    rows, ``out_bytes`` per output element.  Returns (ops, bytes)."""
    ops = 2 * m * k * n
    nbytes = m * k + k * n + 4 * n + (4 * n if bias else 0) + m * n * out_bytes
    return ops, nbytes


def flash_attention(sq: int, skv: int, heads: int, kv_heads: int,
                    head_dim: int, causal: bool = True, elem_bytes: int = 2
                    ) -> tuple[int, int]:
    """One sequence of attention: QK^T and PV over the (causal) pairs.
    Bytes: q and o read/written once, k and v read once."""
    if causal:
        off = skv - sq
        pairs = sum(min(off + i + 1, skv) for i in range(sq))
    else:
        pairs = sq * skv
    ops = 4 * heads * head_dim * pairs
    nbytes = (2 * sq * heads + 2 * skv * kv_heads) * head_dim * elem_bytes
    return ops, nbytes


def layer_gemms(d: Dims, m: int) -> list[tuple[int, int, int, bool]]:
    """The W8A8 GEMMs of one decoder layer at ``m`` rows:
    ``(m, k, n, bias)`` for q, k, v, o, up, gate and down."""
    qd, kvd = d.heads * d.head_dim, d.kv_heads * d.head_dim
    return [(m, d.d_model, qd, d.qkv_bias), (m, d.d_model, kvd, d.qkv_bias),
            (m, d.d_model, kvd, d.qkv_bias), (m, qd, d.d_model, False),
            (m, d.d_model, d.d_ff, False), (m, d.d_model, d.d_ff, False),
            (m, d.d_ff, d.d_model, False)]


def step_gemms(d: Dims, m: int, head_rows: int) -> list[tuple]:
    """Every W8A8 GEMM one forward step runs: the layers at ``m`` rows and
    the LM head at ``head_rows``."""
    return layer_gemms(d, m) * d.layers + [(head_rows, d.d_model, d.vocab,
                                            False)]


def gemms_cost(gemms) -> tuple[int, int]:
    ops = nbytes = 0
    for m, k, n, bias in gemms:
        o, b = nmc_matmul(m, k, n, bias)
        ops += o
        nbytes += b
    return ops, nbytes


def layer_params(d: Dims) -> int:
    """Weights of one layer's GEMMs (the ops per token are twice this)."""
    return sum(k * n for _, k, n, _ in layer_gemms(d, 1))


def useful_ops_prefill(d: Dims, p: int) -> int:
    """What a prompt of ``p`` tokens needs: every layer on every token,
    causal attention, and the LM head on the last position only."""
    attn = flash_attention(p, p, d.heads, d.kv_heads, d.head_dim)[0]
    return p * 2 * layer_params(d) * d.layers + attn * d.layers \
        + 2 * d.d_model * d.vocab


def useful_ops_decode(d: Dims, ctx: int) -> int:
    """One generated token whose attention reads ``ctx`` cached positions
    (its own included)."""
    return 2 * layer_params(d) * d.layers + 2 * d.d_model * d.vocab \
        + 4 * d.heads * d.head_dim * ctx * d.layers
