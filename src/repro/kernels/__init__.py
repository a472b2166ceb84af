"""Pallas TPU kernels (Layer B): nmc_matmul (W8A8 + fused epilogue), vrf_alu
(fused vector-program engine), flash_attention, nmc_expert_matmul (a bank
of W8A8 expert GEMMs in one call) — each with a pure-jnp oracle
in ref.py and a dispatching wrapper in ops.py."""

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.nmc_matmul import nmc_expert_matmul, nmc_matmul
from repro.kernels.vrf_alu import make_prog, vrf_alu

__all__ = ["ops", "ref", "flash_attention", "nmc_matmul",
           "nmc_expert_matmul", "vrf_alu",
           "make_prog"]
