"""Operations and bytes of a latent-attention (MLA) expert model as served,
from its shapes: the GEMMs of a prefill or decode step, the expert bank
kernel ``nmc_expert_matmul``, and the useful work ``serve.mfu`` counts.

As in :mod:`bench.costs`, counts are what the algorithm needs for the
call: rows an expert kernel computes that no token was routed to are its
own inefficiency, and show as a lower roofline share (``moe.pad_share``
counts them).
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int          # all decoder layers
    dense_layers: int    # leading layers with a dense MLP
    d_model: int
    heads: int
    kv_lora: int
    qk_nope: int
    qk_rope: int
    v_head: int
    dense_ff: int
    expert_ff: int
    shared: int          # shared experts, run as one MLP of shared x expert_ff
    experts: int         # the router's width: every routed expert published
    first: int           # the first routed expert held here
    held: int            # routed experts held here
    top_k: int
    vocab: int

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers


def dims(cfg: dict) -> Dims:
    """Model sizes from a configuration file in DeepSeek-V2's keys."""
    held = cfg["experts_held"]
    assert int(held["count"]) == int(cfg["n_routed_experts"]), held
    return Dims(layers=int(cfg["num_hidden_layers"]),
                dense_layers=int(cfg["first_k_dense_replace"]),
                d_model=int(cfg["hidden_size"]),
                heads=int(cfg["num_attention_heads"]),
                kv_lora=int(cfg["kv_lora_rank"]),
                qk_nope=int(cfg["qk_nope_head_dim"]),
                qk_rope=int(cfg["qk_rope_head_dim"]),
                v_head=int(cfg["v_head_dim"]),
                dense_ff=int(cfg["intermediate_size"]),
                expert_ff=int(cfg["moe_intermediate_size"]),
                shared=int(cfg["n_shared_experts"]),
                experts=int(held["published"]), first=int(held["first"]),
                held=int(held["count"]),
                top_k=int(cfg["num_experts_per_tok"]),
                vocab=int(cfg["vocab_size"]))


# ---------------------------------------------------------------------------
# the W8A8 GEMMs (nmc_matmul) of a step
# ---------------------------------------------------------------------------

def attn_gemms(d: Dims, m: int, prefill: bool) -> list[tuple]:
    """MLA's GEMMs at ``m`` rows, ``(m, k, n, bias)``: q, the latent, the
    rope key and the output; prefill also expands the latent into the
    heads' keys and values (decode absorbs those two into attention)."""
    h = d.heads
    out = [(m, d.d_model, h * (d.qk_nope + d.qk_rope), False),
           (m, d.d_model, d.kv_lora, False),
           (m, d.d_model, d.qk_rope, False)]
    if prefill:
        out += [(m, d.kv_lora, h * d.qk_nope, False),
                (m, d.kv_lora, h * d.v_head, False)]
    return out + [(m, h * d.v_head, d.d_model, False)]


def mlp_gemms(d: Dims, m: int, ff: int) -> list[tuple]:
    return [(m, d.d_model, ff, False), (m, d.d_model, ff, False),
            (m, ff, d.d_model, False)]


def step_gemms(d: Dims, m: int, head_rows: int, prefill: bool
               ) -> list[tuple]:
    """Every ``nmc_matmul`` GEMM of one step at ``m`` rows: attention in
    every layer, the dense MLP of the leading layers, the shared experts
    of the others, and the LM head at ``head_rows``.  The prefill of the
    cache recomputes the latent and the rope key once more per layer."""
    attn = attn_gemms(d, m, prefill)
    if prefill:
        attn = attn + attn[1:3]
    return (attn * d.layers
            + mlp_gemms(d, m, d.dense_ff) * d.dense_layers
            + mlp_gemms(d, m, d.shared * d.expert_ff) * d.moe_layers
            + [(head_rows, d.d_model, d.vocab, False)])


# ---------------------------------------------------------------------------
# nmc_expert_matmul
# ---------------------------------------------------------------------------

def expert_bank_gemms(d: Dims) -> list[tuple[int, int]]:
    """``(k, n)`` of each expert's three GEMMs: up, gate and down."""
    return [(d.d_model, d.expert_ff), (d.d_model, d.expert_ff),
            (d.expert_ff, d.d_model)]


def expert_matmul(d: Dims, calls: int, routed_rows: int,
                  out_bytes: int = 2) -> tuple[int, int]:
    """Ops and bytes of ``nmc_expert_matmul`` over ``calls`` expert layers
    (one layer of one program each) whose held experts were routed
    ``routed_rows`` (expert, token) pairs in all: every held expert's int8
    weights and f32 scales read once per call, and the routed rows' int8
    inputs and outputs (``out_bytes`` each; the down GEMM writes f32)."""
    ops = nbytes = 0
    for i, (k, n) in enumerate(expert_bank_gemms(d)):
        ob = 4 if i == 2 else out_bytes
        ops += 2 * routed_rows * k * n
        nbytes += calls * d.held * (k * n + 4 * n) \
            + routed_rows * (k + n * ob)
    return ops, nbytes


# ---------------------------------------------------------------------------
# useful work (serve.mfu)
# ---------------------------------------------------------------------------

def _params(gemms) -> int:
    return sum(k * n for _, k, n, _ in gemms)


def token_ops(d: Dims) -> int:
    """GEMM ops of one token through every layer, the routed experts at
    the expected ``top_k * held / experts`` of a token here (the head
    apart)."""
    attn = _params(attn_gemms(d, 1, prefill=False))
    dense = _params(mlp_gemms(d, 1, d.dense_ff))
    shared = _params(mlp_gemms(d, 1, d.shared * d.expert_ff))
    expert = sum(k * n for k, n in expert_bank_gemms(d))
    routed = expert * d.top_k * d.held / d.experts
    return int(2 * (attn * d.layers + dense * d.dense_layers
                    + (shared + routed) * d.moe_layers))


def latent_attention_ops(d: Dims, ctx: int) -> int:
    """Absorbed MLA for one query over ``ctx`` cached positions, one
    layer: q into the latent, scores over latent and rope key, the
    weighted latent, and out of the latent into the heads' values."""
    h = d.heads
    return 2 * h * (d.qk_nope * d.kv_lora + ctx * (d.kv_lora + d.qk_rope)
                    + ctx * d.kv_lora + d.kv_lora * d.v_head)


def useful_ops_prefill(d: Dims, p: int) -> int:
    """A prompt of ``p`` tokens: every layer on every token (the latent
    expanded into keys and values), causal attention over the expanded
    heads, the head on the last position only."""
    expand = 2 * d.kv_lora * d.heads * (d.qk_nope + d.v_head)
    pairs = p * (p + 1) // 2
    attn = 2 * d.heads * (d.qk_nope + d.qk_rope + d.v_head) * pairs
    return p * (token_ops(d) + expand * d.layers) + attn * d.layers \
        + 2 * d.d_model * d.vocab


def useful_ops_decode(d: Dims, ctx: int) -> int:
    """One generated token whose attention reads ``ctx`` cached positions
    (its own included)."""
    return token_ops(d) + latent_attention_ops(d, ctx) * d.layers \
        + 2 * d.d_model * d.vocab
