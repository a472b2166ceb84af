"""Serving with the paper's technique: int8 near-memory (NMC) execution.

Quantizes a model to the W8A8 serving form (per-channel int8 weights,
dynamic int8 activations, int32 accumulation — the NM-Carus vmacc contract)
and serves a stream of requests with continuous batching, comparing output
agreement and weight-memory footprint against the bf16 baseline.  The
engine calls its jitted prefill/decode programs directly; JAX dispatches
them asynchronously, so admission launches overlap on the device and the
host blocks only on the sampled tokens.

The second half demos the resident-block serving path (DESIGN.md §12):
one decoder layer's quantized weights are DMA'd onto the simulated tile
array once, then every decoded token runs the whole block — q/k/v/o
projections plus the MLP — as chained partitioned waves against the
resident weights, with :class:`repro.nmc.ResidentPool` counters proving
only activation patches cross the bus after the first step.

Run:  PYTHONPATH=src python examples/serve_nmc.py
"""

import numpy as np
import jax

from repro import nmc
from repro.configs import base as cb
from repro.models import lm
from repro.serve.engine import Request, ServeEngine, quantize_params


def tree_bytes(t):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))


def main():
    cfg = cb.get("qwen1.5-0.5b", smoke=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    qcfg = cfg.scaled(nmc_mode="w8a8")
    qparams = quantize_params(params, qcfg)
    print(f"weights: bf16/f32 {tree_bytes(params)/2**20:.1f} MiB -> "
          f"NMC int8 {tree_bytes(qparams)/2**20:.1f} MiB")

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (6, 10, 14, 8)]

    outs = {}
    for name, (c, p) in {"bf16": (cfg, params),
                         "nmc-w8a8": (qcfg, qparams)}.items():
        eng = ServeEngine(c, p, n_slots=2, max_len=64)
        for i, pr in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=pr, max_new=8))
        done = sorted(eng.run(), key=lambda r: r.rid)
        outs[name] = [r.out for r in done]
        print(f"{name:9s}: {[o[:6] for o in outs[name]]}")

    agree = np.mean([np.mean(np.array(a) == np.array(b))
                     for a, b in zip(outs["bf16"], outs["nmc-w8a8"])])
    print(f"\ntoken agreement bf16 vs NMC-int8: {100*agree:.0f}%")

    # -- resident-block serving (DESIGN.md §12) ------------------------------
    # keep one decoder layer's W8A8 weights resident on the tile array and
    # decode against them: weights DMA once, every later step patches only
    # the activation scalar-tap words
    own = nmc.DispatchQueue(pool=nmc.ResidentPool(
        pool=nmc.default_runtime().bucketed))
    eng = ServeEngine(qcfg, qparams, n_slots=4, max_len=64,
                      nmc_queue=own, nmc_tiles=4)
    blk = eng.resident_block(layer=0, tiles=4)
    x = rng.normal(size=(4, qcfg.d_model)).astype(np.float32)
    xj = x.copy()
    st, stj = blk.init_state(16), blk.init_state(16)
    print(f"\nresident block: {blk.n_shards} tile shards, "
          f"static layout proof: {blk.static}")
    for step in range(3):
        x, st = blk.step(x, st)                    # resident tile array
        xj, stj = blk.step(xj, stj, mm=blk.jax_mm)  # pure-JAX int32 reference
        assert np.array_equal(x, xj), "resident path diverged from reference"
        print(f"  step {step}: loads={own.pool.loads} "
              f"(weight DMAs — constant after step 0), "
              f"patch_bytes={own.pool.patch_bytes} "
              f"(+{blk.patch_bytes_per_call}/step), bit-exact vs JAX: "
              f"{np.array_equal(x, xj)}")
    print(f"steady-state block step: {blk.step_cycles(steady=True):.0f} "
          f"modeled cycles (cold: {blk.step_cycles(steady=False):.0f})")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
