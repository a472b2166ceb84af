"""Ahead-of-time compiles of the main-path kernels for a TPU v5e that is
described, not attached.

The TPU compiler runs here without a chip: each case lowers and compiles a
kernel at the shape the system really runs, so a tiling, SMEM or VMEM fault
that interpret mode cannot see fails here.  Nothing runs; a compile that
passes says nothing about results or times.

* the Pallas tile engines at SEW 8/16/32 and tile bucket 4, at the
  largest instruction bucket the registry kernels and the resident block
  produce and at the smallest (a stream shorter than one chunk), and the
  name each engine's custom call carries (``caesar_sew8`` ...);
* ``nmc_matmul`` at the five qwen1.5-0.5b projection shapes, at decode
  (M = 4 slots) and at one prefill length;
* ``flash_attention`` at prompt lengths 7 and 200;
* ``nmc_expert_matmul`` at DeepSeek-V2-Lite's expert banks (8 experts,
  2048 x 1408 and back) at 96 rows (decode) and 512 (the longest
  prefill), with the kernel's name;
* ``ResidentPool.dispatch``'s group call (stack, engine kernel, split) at
  16 tiles, with the kernel's name and no donated input.

The topology is described inside a module fixture (never at import): only
the worker that runs this file loads the TPU library.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.nmc_matmul import nmc_expert_matmul, nmc_matmul
from repro.nmc import pallas_engine as pe

SEWS = (8, 16, 32)
LARGEST_BUCKET = 16384      # Table V gemm / resident-block Caesar streams
TILE_BUCKET = 4
# qwen1.5-0.5b (configs/qwen1_5_0_5b.py): d_model 1024, d_ff 2816, vocab
# 151936; (K, N, bias, act) per projection
QWEN_PROJECTIONS = {
    "qkv": (1024, 1024, True, "none"),
    "o": (1024, 1024, False, "none"),
    "up": (1024, 2816, False, "none"),
    "gate": (1024, 2816, False, "silu"),
    "down": (2816, 1024, False, "none"),
    "head": (1024, 151936, False, "none"),
}
QWEN_HEADS, QWEN_HEAD_DIM = 16, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()     # the kernel is there
    return compiled


@pytest.mark.parametrize("n_instr", (1, LARGEST_BUCKET),
                         ids=("smallest", "largest"))
@pytest.mark.parametrize("sew", SEWS)
@pytest.mark.parametrize("engine", ("caesar", "carus"))
def test_engine_wave_compiles(one_chip, engine, sew, n_instr):
    if engine == "caesar":
        state = _sds(one_chip, (TILE_BUCKET, 8192))
        fields, wave = pe.CAESAR_FIELDS, pe.caesar_wave
    else:
        state = _sds(one_chip, (TILE_BUCKET, 32, 256))
        fields, wave = pe.CARUS_FIELDS, pe.carus_wave
    stream = {f: _sds(one_chip, (TILE_BUCKET, n_instr)) for f in fields}
    _compile(functools.partial(wave, sew=sew, interpret=False), state, stream)


@pytest.mark.parametrize("sew", (8, 32))
@pytest.mark.parametrize("engine", ("caesar", "carus"))
def test_engine_kernel_carries_its_name(one_chip, engine, sew):
    """The engine's custom call is named ``<engine>_sew<SEW>`` in the
    compiled HLO, so the trace's operation name (that HLO line) is matched
    by name, not by the unnamed-call shape fallback of ``bench/kernels``."""
    from bench.kernels import is_kernel
    if engine == "caesar":
        state = _sds(one_chip, (TILE_BUCKET, 8192))
        fields, wave = pe.CAESAR_FIELDS, pe.caesar_wave
    else:
        state = _sds(one_chip, (TILE_BUCKET, 32, 256))
        fields, wave = pe.CARUS_FIELDS, pe.carus_wave
    stream = {f: _sds(one_chip, (TILE_BUCKET, 1)) for f in fields}
    text = _compile(functools.partial(wave, sew=sew, interpret=False),
                    state, stream).as_text()
    calls = [ln.strip() for ln in text.splitlines()
             if "tpu_custom_call" in ln and " custom-call(" in ln]
    assert calls
    other = "carus" if engine == "caesar" else "caesar"
    for op in calls:
        assert op.startswith(f"%{engine}_sew{sew}."), op[:80]
        assert is_kernel(engine)(op) and not is_kernel(other)(op)


@pytest.mark.parametrize("engine", ("caesar", "carus"))
def test_resident_group_call_compiles(one_chip, monkeypatch, engine):
    """``ResidentPool.dispatch``'s one call per bucket group, at the
    benchmark's 16 tiles: it compiles, holds one engine kernel under the
    engine's name, and aliases no input to an output (nothing donated)."""
    from repro.nmc import engine as nmc_engine
    from repro.nmc.pool import BucketedPool
    eng = (pe.PallasCaesarEngine if engine == "caesar"
           else pe.PallasCarusEngine)()
    eng.interpret = False
    monkeypatch.setattr(nmc_engine, "_DEFAULT_ENGINES",
                        {(engine, "pallas"): eng})
    tiles, bucket = 16, 1024
    fn = BucketedPool(backend="pallas")._group_fn(
        (engine, 8, bucket), tiles)
    shape = (8192,) if engine == "caesar" else (32, 256)
    n_fields = len(pe.CAESAR_FIELDS if engine == "caesar"
                   else pe.CARUS_FIELDS)
    text = fn.lower(tuple(_sds(one_chip, shape) for _ in range(tiles)),
                    _sds(one_chip, (n_fields, tiles, bucket))
                    ).compile().as_text()
    calls = [ln.strip() for ln in text.splitlines()
             if "tpu_custom_call" in ln and " custom-call(" in ln]
    assert len(calls) == 1 and f"%{engine}_sew8." in calls[0], calls
    assert "input_output_alias" not in text.splitlines()[0]


@pytest.mark.parametrize("m", (4, 200), ids=("decode", "prefill"))
@pytest.mark.parametrize("proj", sorted(QWEN_PROJECTIONS))
def test_nmc_matmul_compiles_at_qwen_widths(one_chip, proj, m):
    k, n, bias, act = QWEN_PROJECTIONS[proj]
    args = [_sds(one_chip, (m, k), jnp.int8), _sds(one_chip, (k, n), jnp.int8),
            _sds(one_chip, (n,), jnp.float32)]
    if bias:
        args.append(_sds(one_chip, (n,), jnp.float32))
    _compile(functools.partial(nmc_matmul, act=act,
                               out_dtype=jnp.bfloat16), *args)


@pytest.mark.parametrize("s", (7, 200))
def test_flash_attention_compiles_at_prompt_length(one_chip, s):
    shape = (1, QWEN_HEADS, s, QWEN_HEAD_DIM)
    qkv = [_sds(one_chip, shape, jnp.bfloat16)] * 3
    _compile(functools.partial(flash_attention, causal=True), *qkv)


@pytest.mark.parametrize("m", (96, 512), ids=("decode", "prefill"))
@pytest.mark.parametrize("k,n,act", ((2048, 1408, "silu"),
                                     (1408, 2048, "none")),
                         ids=("up", "down"))
def test_nmc_expert_matmul_compiles_at_deepseek_widths(one_chip, m, k, n,
                                                       act):
    """The held experts' banks of one DeepSeek-V2-Lite layer (8 of 64)
    in one call; the custom call is named ``nmc_expert_matmul``, which
    ``nmc_matmul``'s trace name does not match."""
    from bench.kernels import is_kernel
    args = [_sds(one_chip, (8, m, k), jnp.int8),
            _sds(one_chip, (8, k, n), jnp.int8),
            _sds(one_chip, (8, n), jnp.float32)]
    text = _compile(functools.partial(nmc_expert_matmul, act=act,
                                      out_dtype=jnp.bfloat16),
                    *args).as_text()
    calls = [ln.strip().removeprefix("ROOT ") for ln in text.splitlines()
             if "tpu_custom_call" in ln and " custom-call(" in ln]
    assert len(calls) == 1 and calls[0].startswith("%nmc_expert_matmul.")
    assert not is_kernel("nmc_matmul")(calls[0])
