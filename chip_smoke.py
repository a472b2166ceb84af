"""Bring-up check: the main paths run on one TPU, bit-exact.

    python chip_smoke.py [--seed N]

One process, no children.  Phases, in order:

  (a) device      — a TPU is attached; both NMC engines resolve to native
                    Pallas (never interpret mode) and the LM kernels to Pallas
  (b) engines     — the Table V kernels on both engines at SEW 8/16/32 through
                    the bucketed pool, bit-exact vs their oracles and vs the
                    scan engines on the same chip; plus random programs over
                    every opcode, Pallas vs scan
  (c) waves       — an ``nmc.jit`` wave at tiles=4 and the mixed Caesar+Carus
                    qrelu wave as one launch, bit-exact
  (d) resident    — the qwen1.5-0.5b layer-0 W8A8 block (smoke widths) resident
                    on 4 tiles for 3 steps, bit-exact vs the int32 JAX GEMMs,
                    weights loaded once
  (e) serving     — ``ServeEngine`` on qwen1.5-0.5b at its published widths,
                    W8A8, random weights from ``--seed``: 6 requests, prompts of
                    5..200 tokens, 8 new tokens each, finite logits
  (f) kernels     — ``nmc_matmul`` and ``flash_attention`` at every shape (e)
                    called them with, against ``kernels/ref.py``

Each phase prints its time and compile count.  Any failure raises: the
script then exits non-zero and prints no result.  The last line is the
JSON result ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax

SEWS = (8, 16, 32)
PROMPT_LENS = (5, 17, 64, 111, 160, 200)
MAX_NEW = 8
N_SLOTS = 4


class Compiles:
    """Counts XLA backend compiles and persistent-cache hits."""

    def __init__(self):
        self.n, self.secs, self.hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.n, self.secs, self.hits


def phase(name: str, compiles: Compiles):
    def wrap(fn):
        def run(*a, **kw):
            n0, s0, h0 = compiles.snapshot()
            t0 = time.perf_counter()
            out = fn(*a, **kw)
            n1, s1, h1 = compiles.snapshot()
            print(f"phase {name}: {time.perf_counter() - t0:.3f} s, "
                  f"{n1 - n0} compiles ({s1 - s0:.3f} s), "
                  f"{h1 - h0} cache hits", flush=True)
            return out
        return run
    return wrap


# ---------------------------------------------------------------------------
# (a) device
# ---------------------------------------------------------------------------

def check_device():
    from repro.kernels import ops
    from repro.nmc.engine import get_engine
    from repro.nmc.pallas_engine import PallasCaesarEngine, PallasCarusEngine

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    for name, cls in (("caesar", PallasCaesarEngine),
                      ("carus", PallasCarusEngine)):
        eng = get_engine(name, "auto")
        assert isinstance(eng, cls), (name, type(eng))
        assert eng.interpret is False, name
    assert ops.backend_is_tpu()


# ---------------------------------------------------------------------------
# (b) engines
# ---------------------------------------------------------------------------

def _random_caesar(rng, sew, n):
    from repro.core.isa import CaesarOp
    from repro.nmc.program import Program, caesar_entry
    ops = list(CaesarOp)
    return Program.from_entries("caesar", sew, [
        caesar_entry(ops[rng.integers(len(ops))], int(rng.integers(8192)),
                     int(rng.integers(8192)), int(rng.integers(8192)))
        for _ in range(n)])


def _random_carus(rng, sew, n):
    from repro.core import isa
    from repro.core.isa import VOp
    from repro.nmc.program import Program, carus_entry
    vops = list(isa.VOP_COMPACT)
    entries = []
    for _ in range(n):
        vop = vops[rng.integers(len(vops))]
        # vv/vx/vi, plus indirect addressing and slide1 bits
        mode = int(rng.integers(3)) | int(rng.choice([0, isa.MODE_INDIRECT])) \
            | int(rng.choice([0, isa.MODE_SLIDE1]))
        if vop == VOp.VSETVL:
            sval1 = int(rng.integers(-4, 1024 + 17))
        elif vop in (VOp.VSLIDEUP, VOp.VSLIDEDOWN):
            sval1 = int(rng.integers(-1100, 1100))   # both edges clip
        else:
            sval1 = int(rng.integers(-2**31, 2**31))
        entries.append(carus_entry(
            vop, vd=int(rng.integers(32)), vs1=int(rng.integers(32)),
            vs2=int(rng.integers(32)), sval1=sval1,
            sval2=int(rng.integers(-2**31, 2**31)),
            imm=int(rng.integers(-16, 16)), mode=mode))
    return Program.from_entries("carus", sew, entries)


def check_engines(seed: int):
    import numpy as np
    from repro import nmc
    from repro.core import programs
    from repro.nmc.engine import get_engine

    kbs = [programs.build(name, sew) for name in programs.TABLE_V_KERNELS
           for sew in SEWS]
    builds = [getattr(kb, e) for kb in kbs for e in ("caesar", "carus")]
    pallas = nmc.ResidentPool()
    assert pallas.pool.backend == "pallas"
    got = pallas.run_builds(builds)
    ref = nmc.ResidentPool(backend="scan").run_builds(builds)
    for eb, g, r in zip(builds, got, ref):
        exp = np.asarray(eb.oracle).reshape(-1)
        assert np.array_equal(np.asarray(g).reshape(-1)[:exp.size], exp), \
            eb.program.bucket_key
        assert np.array_equal(g, r), eb.program.bucket_key
    print(f"  table V: {len(builds)} programs, {pallas.compiles} Pallas "
          f"kernels, bit-exact vs oracle and scan")

    rng = np.random.default_rng(seed)
    n = 0
    for sew in SEWS:
        for make, shape in ((_random_caesar, (8192,)),
                            (_random_carus, (32, 256))):
            for _ in range(2):
                prog = make(rng, sew, 200)
                state = rng.integers(-2**31, 2**31, shape,
                                     dtype=np.int64).astype(np.int32)
                outs = []
                for backend in ("pallas", "scan"):
                    eng = get_engine(prog.engine, backend)
                    outs.append(np.asarray(
                        eng.run(eng.init_state(state), prog)))
                assert np.array_equal(*outs), (prog.engine, sew)
                n += 1
    print(f"  every opcode: {n} random programs, Pallas == scan")


# ---------------------------------------------------------------------------
# (c) partitioned waves
# ---------------------------------------------------------------------------

def check_waves(seed: int):
    import numpy as np
    from repro import nmc
    from repro.core import programs

    rt, rt_scan = nmc.NmcRuntime(), nmc.NmcRuntime(backend="scan")
    assert rt.backend == "pallas"
    rng = np.random.default_rng(seed)
    A = rng.integers(-128, 128, (8, 4), dtype=np.int8)
    B = rng.integers(-128, 128, (4, 48), dtype=np.int8)

    def mm(t, A, B):
        a = t.consts(A)
        rows = [t.load(B[r]) for r in range(4)]
        for i in range(8):
            acc = None
            for kk in range(4):
                acc = nmc.mac(acc, a[i, kk], rows[kk])
            t.store(acc)

    exp = (A.astype(np.int64) @ B.astype(np.int64)).astype(np.int8)
    for engine in ("caesar", "carus"):
        got = np.asarray(nmc.jit(mm, runtime=rt, tiles=4)(A, B,
                                                          engine=engine))
        ref = np.asarray(nmc.jit(mm, runtime=rt_scan, tiles=4)(
            A, B, engine=engine))
        assert np.array_equal(got, exp) and np.array_equal(got, ref), engine

    kfn, args = programs.qrelu_case(8)
    ck = nmc.jit(kfn, tiles=8, partition="rows", runtime=rt)
    q = rt.queue
    w0, m0 = q.waves, q.mixed_engine_waves
    out = np.asarray(ck(*args, schedule="auto"))
    assert q.waves - w0 == 1 and q.mixed_engine_waves - m0 == 1
    ref = np.asarray(nmc.jit(kfn, tiles=8, partition="rows",
                             runtime=rt_scan)(*args, schedule="auto"))
    assert np.array_equal(out, ck.oracle(*args))
    assert np.array_equal(out, ref)
    print("  tiles=4 matmul wave on both engines and the mixed qrelu wave: "
          "bit-exact")


# ---------------------------------------------------------------------------
# (d) resident block
# ---------------------------------------------------------------------------

def check_resident_block(seed: int):
    import numpy as np
    from repro import nmc
    from repro.configs import base as cb
    from repro.models import lm
    from repro.serve.engine import ServeEngine, quantize_params

    cfg = cb.get("qwen1.5-0.5b", smoke=True).scaled(nmc_mode="w8a8")
    params = quantize_params(lm.init_params(jax.random.PRNGKey(seed), cfg),
                             cfg)
    queue = nmc.DispatchQueue()
    eng = ServeEngine(cfg, params, n_slots=N_SLOTS, max_len=32,
                      nmc_queue=queue, nmc_tiles=4)
    blk = eng.resident_block(layer=0, rows=N_SLOTS, tiles=4)
    x0 = np.random.default_rng(seed).normal(
        size=(N_SLOTS, cfg.d_model)).astype(np.float32)

    def chain(mm):
        x, st, outs = x0.copy(), blk.init_state(16), []
        for _ in range(3):
            x, st = blk.step(x, st, mm=mm)
            outs.append(x.copy())
        return outs

    res = chain(None)
    assert blk.static, "value-independence proof failed"
    assert queue.pool.loads == blk.n_shards, (queue.pool.loads, blk.n_shards)
    ref = chain(blk.jax_mm)
    assert queue.pool.loads == blk.n_shards      # weights crossed once
    assert all(np.array_equal(a, b) for a, b in zip(res, ref))
    print(f"  3 steps on {blk.n_shards} resident shards, "
          f"{queue.pool.loads} loads, bit-exact vs jax_mm")


# ---------------------------------------------------------------------------
# (e) full-width serving, recording the kernels it calls
# ---------------------------------------------------------------------------

def published_config():
    from repro.configs import base as cb

    cfg = cb.get("qwen1.5-0.5b").scaled(nmc_mode="w8a8")
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size) == \
        (24, 1024, 2816, 151936)
    return cfg


def serve(cfg, seed: int) -> tuple[set, set]:
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.models import lm
    from repro.serve.engine import Request, ServeEngine, quantize_params

    params = jax.jit(lambda k: quantize_params(lm.init_params(k, cfg), cfg))(
        jax.random.PRNGKey(seed))

    # record every Pallas kernel call the served model traces
    mm_calls, fa_calls = set(), set()
    mm_fn, fa_fn = ops._mm.nmc_matmul, ops._fa.flash_attention

    def mm_rec(x_q, w_q, scale, bias=None, **kw):
        mm_calls.add((x_q.shape, w_q.shape, bias is not None, kw["act"],
                      jnp.dtype(kw["out_dtype"]).name))
        return mm_fn(x_q, w_q, scale, bias, **kw)

    def fa_rec(q, k, v, **kw):
        fa_calls.add((q.shape, k.shape, v.shape, q.dtype.name,
                      tuple(sorted(kw.items()))))
        return fa_fn(q, k, v, **kw)

    ops._mm.nmc_matmul, ops._fa.flash_attention = mm_rec, fa_rec
    eng = ServeEngine(cfg, params, n_slots=N_SLOTS, max_len=256)
    finite = []
    for attr in ("prefill", "decode"):
        def checked(*a, _fn=getattr(eng, attr)):
            logits, caches = _fn(*a)
            finite.append(jnp.isfinite(logits).all())
            return logits, caches
        setattr(eng, attr, checked)

    rng = np.random.default_rng(seed)
    for rid, n in enumerate(PROMPT_LENS):
        eng.submit(Request(rid, rng.integers(0, cfg.vocab_size, n,
                                             dtype=np.int32),
                           max_new=MAX_NEW))
    t0 = time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    ops._mm.nmc_matmul, ops._fa.flash_attention = mm_fn, fa_fn

    assert sorted(r.rid for r in done) == list(range(len(PROMPT_LENS)))
    assert all(len(r.out) == MAX_NEW for r in done), \
        [len(r.out) for r in done]
    assert all(bool(f) for f in finite), "non-finite logits"
    assert mm_calls and fa_calls, "served W8A8 path missed the Pallas kernels"
    print(f"  {cfg.name}: {len(done)} requests x {MAX_NEW} tokens "
          f"in {wall:.3f} s (compiles included); {len(finite)} finite logit "
          f"batches; {len(mm_calls)} nmc_matmul and {len(fa_calls)} "
          f"flash_attention shapes")
    return mm_calls, fa_calls


# ---------------------------------------------------------------------------
# (f) kernel parity at the served shapes
# ---------------------------------------------------------------------------

def _mm_parity(x, w, scale, bias, act, out):
    """Pallas ``nmc_matmul`` vs ``ref.nmc_matmul`` on the given int8
    operands: (int32 accumulation equal, max |err| / (|ref| + 1) of the
    served epilogue).  The reference may keep its f32 epilogue unrounded
    (XLA allows excess precision), so the bound is one ulp of ``out``."""
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.nmc_matmul import nmc_matmul

    one = jnp.ones_like(scale)
    exact = jnp.all(nmc_matmul(x, w, one, None, out_dtype=jnp.float32)
                    == ref.nmc_matmul(x, w, one, None, out_dtype=jnp.float32))
    got = nmc_matmul(x, w, scale, bias, act=act, out_dtype=out)
    exp = ref.nmc_matmul(x, w, scale, bias, act=act, out_dtype=out)
    exp = exp.astype(jnp.float32)
    return exact, jnp.max(jnp.abs(got.astype(jnp.float32) - exp)
                          / (jnp.abs(exp) + 1))


def _fa_parity(key, qs, ks, vs, dt, kw):
    """Pallas ``flash_attention`` vs ``ref.attention``: max |err|."""
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention

    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, qs).astype(dt)
    k = jax.random.normal(k2, ks).astype(dt)
    v = jax.random.normal(k3, vs).astype(dt)
    got = flash_attention(q, k, v, **dict(kw)).astype(jnp.float32)
    exp = ref.attention(q, k, v, **dict(kw)).astype(jnp.float32)
    return jnp.max(jnp.abs(got - exp))


def check_kernels(mm_calls: set, fa_calls: set, seed: int):
    import numpy as np
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    mm = jax.jit(_mm_parity, static_argnums=(4, 5))
    fa = jax.jit(_fa_parity, static_argnums=(1, 2, 3, 4, 5))
    worst_mm = worst_fa = 0.0
    # operands drawn on the host, one weight per shape: a device draw of
    # the 1024 x 151936 head weight takes ~25 s to compile per call shape
    weights = {}
    for sig in sorted(mm_calls):
        xs, ws, has_bias, act, out = sig
        if ws not in weights:
            n = ws[1]
            weights[ws] = (
                jnp.asarray(rng.integers(-127, 128, ws, dtype=np.int8)),
                jnp.asarray(rng.uniform(1e-5, 1e-4, n).astype(np.float32)),
                jnp.asarray(rng.normal(size=n).astype(np.float32)))
        w, scale, bias = weights[ws]
        x = jnp.asarray(rng.integers(-127, 128, xs, dtype=np.int8))
        exact, err = mm(x, w, scale, bias if has_bias else None, act, out)
        assert bool(exact), sig                 # int32 accumulation exact
        assert float(err) <= jnp.finfo(sig[-1]).eps, (sig, float(err))
        worst_mm = max(worst_mm, float(err))
    for i, sig in enumerate(sorted(fa_calls)):
        err = float(fa(jax.random.fold_in(key, 1000 + i), *sig))
        assert err <= 5e-2, (sig, err)
        worst_fa = max(worst_fa, err)
    print(f"  nmc_matmul: {len(mm_calls)} shapes, int32 accumulation exact, "
          f"epilogue max |err|/(|ref|+1) {worst_mm:.3g}; flash_attention: "
          f"{len(fa_calls)} shapes, max |err| {worst_fa:.3g}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, data and programs")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU attached (JAX found {dev.platform!r})",
              file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.launch.cache import cache_entries, enable_compile_cache

    cache = enable_compile_cache()
    print(f"compile cache: {cache} ({cache_entries(cache)} entries)")
    compiles = Compiles()
    phase("a device", compiles)(check_device)()
    phase("b engines", compiles)(check_engines)(args.seed)
    phase("c waves", compiles)(check_waves)(args.seed)
    phase("d resident", compiles)(check_resident_block)(args.seed)
    calls = phase("e serving", compiles)(serve)(published_config(),
                                                args.seed)
    phase("f kernels", compiles)(check_kernels)(*calls, args.seed)
    print(f"compile cache: {cache} ({cache_entries(cache)} entries)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
