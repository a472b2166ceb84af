"""The NMC drivers, their references, their control and planted faults,
at sizes a CPU test run can hold (scan engines stand in for the chip)."""

import json
import time

import numpy as np
import pytest

from bench import harness as H
from bench.drivers import nmc_jit_calls, nmc_library as lib, nmc_waves
from bench.ref import nmc as ref

CFG = json.loads((H.BENCH / "configs" / "nmc-edge.json").read_text())
TABLE_V = H.load_traffic("tablev-waves")
JIT = H.load_traffic("jit-calls")
SMALL_CFG = dict(CFG, n_tiles=4)
SMALL_WAVES = dict(TABLE_V, kernels=["xor", "gemm", "conv2d", "maxpool"],
                   sews=[8])
SMALL_JIT = dict(JIT, families=["mul", "matmul", "maxpool"], sews=[8],
                 tiles=[1, 4])


def _harness(seconds=0.3):
    return H.Harness(seconds, time.perf_counter())


@pytest.mark.parametrize("sew", [8, 16, 32])
def test_table_v_references_match_the_program(sew):
    insts, lowered = nmc_waves.build_library(
        CFG, dict(TABLE_V, sews=[sew]), seed=2**31 + 5)
    assert len(lowered) == 9 * 2
    for inst, lk in zip(insts, lowered):
        want = ref.reference(inst.kind, inst.args, sew, inst.params)
        assert ref.mismatches(lk.oracle, want) == 0, (inst.kind, lk.engine)


def test_jit_family_references_match_the_program():
    rng = np.random.default_rng(3)
    for kind in JIT["families"]:
        for sew in JIT["sews"]:
            inst = lib.instance(kind, sew, JIT["shapes"][kind], rng)
            from repro import nmc
            got = nmc.jit(inst.fn, sew=sew).oracle(*inst.args)
            assert ref.mismatches(got, ref.reference(
                inst.kind, inst.args, sew, inst.params)) == 0, (kind, sew)


def test_library_is_deterministic_per_seed():
    a, _ = nmc_waves.build_library(CFG, SMALL_WAVES, seed=9)
    b, _ = nmc_waves.build_library(CFG, SMALL_WAVES, seed=9)
    c, _ = nmc_waves.build_library(CFG, SMALL_WAVES, seed=10)
    flat = [lambda x: np.concatenate([np.ravel(v) for v in x.args])]
    for f in flat:
        assert all(np.array_equal(f(x), f(y)) for x, y in zip(a, b))
        assert not all(np.array_equal(f(x), f(y)) for x, y in zip(a, c))


def test_waves_driver_end_to_end():
    res = nmc_waves.run(_harness(), SMALL_CFG, SMALL_WAVES, seed=2**33 + 1)
    assert res.correct, [(c.name, c.value) for c in res.compared]
    assert res.end_to_end["nmc_kernels_per_s"] > 0
    assert res.attempted % 8 == 0 and res.attempted >= 8
    assert res.facts["useful_instrs"] > 0


def test_jit_calls_driver_end_to_end():
    res = nmc_jit_calls.run(_harness(), CFG, SMALL_JIT, seed=11)
    assert res.correct, [(c.name, c.value) for c in res.compared]
    assert res.attempted >= 1
    assert len(res.facts["frontend_ms"]) == res.attempted


def test_control_breaks_the_wrap_guarantee():
    """The control (saturating arithmetic in the program's place) fails
    the comparison on every seed, at the cell's own sizes."""
    for seed in (1, 2, 3):
        insts, _ = nmc_waves.build_library(CFG, TABLE_V, seed)
        bad = sum(ref.mismatches(
            ref.reference(i.kind, i.args, i.sew, i.params, saturate=True),
            ref.reference(i.kind, i.args, i.sew, i.params)) for i in insts)
        assert bad > 0, seed


def _unchanged_state(self, shape_key, n_tiles, backend=None):
    return lambda state, arrays: state


def _altering(orig, every):
    seen = [0]

    def result(self):
        out = orig(self)
        seen[0] += 1
        if out is not None and np.size(out) and seen[0] % every == 1:
            out = np.array(out, copy=True)
            out.flat[0] = out.flat[0] ^ 1
        return out
    return result


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered"])
@pytest.mark.parametrize("driver", ["waves", "jit_calls"])
def test_planted_fault_reads_incorrect(monkeypatch, driver, fault):
    from repro.nmc import pool, runtime
    if fault == "state_unchanged":
        monkeypatch.setattr(pool.TilePool, "_batched_fn", _unchanged_state)
    else:
        monkeypatch.setattr(runtime.NMCFuture, "result",
                            _altering(runtime.NMCFuture.result, every=5))
    if driver == "waves":
        res = nmc_waves.run(_harness(), SMALL_CFG, SMALL_WAVES, seed=4)
    else:
        res = nmc_jit_calls.run(_harness(), CFG, SMALL_JIT, seed=4)
    assert not res.correct
    assert res.compared[0].value > 0
