"""Compile the serving cells' programs for a described TPU v5e, no chip.

    JAX_PLATFORMS=cpu python bench/aot_serving.py

For each serving traffic mix: the decode step at ``n_slots`` x
``max_len`` and the prefill of its largest prompt bucket, compiled by the
TPU compiler for one chip of a described ``v5e:2x2``, with the Pallas
kernels the chip runs.  Prints ``memory_analysis()`` of each: arguments,
outputs, temporaries and what is aliased, so the KV-cache sizes of the
cells can be checked before any chip time is spent.
"""

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

os.environ.setdefault("TPU_LOG_DIR", "disabled")

GIB = 2 ** 30


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import costs, harness as H
    from bench.drivers import lm_weights
    from repro.kernels import ops
    from repro.models import lm
    from repro.serve.engine import make_decode_step, make_prefill_step

    jax.config.update("jax_enable_compilation_cache", False)
    ops._BACKEND_IS_TPU = True          # trace the chip's Pallas kernels
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def placed(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    bm = H.load_benchmark()
    for w in bm["workloads"]:
        traffic = H.load_traffic(w["traffic"])
        if traffic["driver"] != "serve_closed_loop":
            continue
        cfg = H.load_config(H.find_cell(bm, w["name"])[1])
        mcfg = lm_weights.model_config(cfg)
        d = costs.dims(cfg)
        params = placed(jax.eval_shape(
            lambda: lm_weights._make(lm_weights.key_of(0), d, True)))
        slots, max_len = int(traffic["n_slots"]), int(traffic["max_len"])
        caches = placed(jax.eval_shape(
            lambda: lm.init_caches(None, mcfg, slots, max_len,
                                   dtype=jnp.bfloat16)))
        kv = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(caches))
        wb = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
        print(f"{w['name']}: weights {wb / GIB:.3f} GiB, KV cache "
              f"{kv / GIB:.3f} GiB ({slots} x {max_len})")
        i32 = jnp.int32
        dec = jax.jit(make_decode_step(mcfg), donate_argnums=(2,)).lower(
            params, placed(jax.ShapeDtypeStruct((slots, 1), i32)), caches,
            placed(jax.ShapeDtypeStruct((slots,), i32))).compile()
        report(f"  decode {slots} x {max_len}", dec)
        p = max(traffic["prompt_buckets"])
        pre = jax.jit(make_prefill_step(mcfg, max_len)).lower(
            params, {"tokens": placed(jax.ShapeDtypeStruct((1, p), i32))}
        ).compile()
        report(f"  prefill 1 x {p}", pre)
    return 0


def report(what: str, compiled) -> None:
    m = compiled.memory_analysis()
    print(f"{what}: arguments {m.argument_size_in_bytes / GIB:.3f} GiB, "
          f"outputs {m.output_size_in_bytes / GIB:.3f} GiB, temporaries "
          f"{m.temp_size_in_bytes / GIB:.3f} GiB, aliased "
          f"{m.alias_size_in_bytes / GIB:.3f} GiB, code "
          f"{m.generated_code_size_in_bytes / 2**20:.1f} MiB; pallas "
          f"kernels: {compiled.as_text().count('tpu_custom_call')}",
          flush=True)


if __name__ == "__main__":
    sys.exit(main())
