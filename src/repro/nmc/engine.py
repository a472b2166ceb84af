"""Engine protocol: one interface over both NMC tiles (DESIGN.md §5).

An :class:`Engine` knows how to *lower* a unified-IR
:class:`repro.nmc.program.Program` to the arrays its scan consumes, *run* it
against a tile state (Caesar: flat memory words; Carus: the VRF), *extract*
output elements from a final state, and *cost* it through the mechanistic
timing/energy models.  The two implementations wrap the existing functional
simulators — the scans themselves are unchanged and stay bit-exact.

``scan_fn(sew)`` returns the raw ``(state, arrays) -> state`` callable the
:class:`repro.nmc.pool.TilePool` maps over tiles with ``jax.vmap`` (on the
scan backend).  The bucketed scheduler feeds NOP-padded streams through the
same callable:
``CaesarOp.NOP`` / ``VOp.VNOP`` entries leave the carried state bit-exactly
unchanged inside both scans, so a padded program's final state equals the
unpadded one's (property-tested in ``tests/test_nmc_ir.py``).
"""

from __future__ import annotations

from typing import Protocol, runtime_checkable

import jax.numpy as jnp
import numpy as np

from repro.core import alu
from repro.core.caesar import CaesarConfig, CaesarEngine
from repro.core.carus import CarusConfig, CarusVPU
from repro.nmc.program import Program


@runtime_checkable
class Engine(Protocol):
    name: str

    def lower(self, program: Program) -> dict: ...
    def init_state(self, image) -> jnp.ndarray: ...
    def run(self, state, program: Program): ...
    def scan_fn(self, sew: int): ...
    def extract(self, state, out_slice: tuple[int, int], sew: int): ...
    def cost(self, program: Program, host_cycles: float = 0.0): ...


class _EngineBase:
    def lower(self, program: Program) -> dict:
        assert program.engine == self.name, (program.engine, self.name)
        return program.lower()

    def extract(self, state, out_slice: tuple[int, int], sew: int
                ) -> np.ndarray:
        """Final tile state -> output elements (host-side view)."""
        start, nw = out_slice
        flat = np.asarray(state).reshape(-1)
        return alu.unpack_np(flat[start:start + nw], alu.NP_DTYPES[sew])

    def cost(self, program: Program, host_cycles: float = 0.0):
        from repro.core import timing
        return timing.program_cycles(program, host_cycles)

    def energy(self, program: Program, host_cycles: float = 0.0):
        from repro.core import energy
        return energy.program_energy(program, host_cycles)


class CaesarTile(_EngineBase):
    """NM-Caesar tile: state is the flat 8192-word 2-bank memory image."""

    name = "caesar"

    def __init__(self, config: CaesarConfig | None = None):
        self.sim = CaesarEngine(config)

    def init_state(self, image) -> jnp.ndarray:
        return jnp.asarray(image, jnp.int32).reshape(-1)

    def run(self, state, program: Program):
        mem, _, _ = self.sim.run_program(state, program)
        return mem

    def scan_fn(self, sew: int):
        def run_one(mem, arrays):
            out, _, _ = self.sim.run_stream(mem, arrays, sew)
            return out
        return run_one


class CarusTile(_EngineBase):
    """NM-Carus tile: state is the (n_regs, reg_words) VRF."""

    name = "carus"

    def __init__(self, config: CarusConfig | None = None):
        self.sim = CarusVPU(config)

    def init_state(self, image) -> jnp.ndarray:
        cfg = self.sim.cfg
        return jnp.asarray(image, jnp.int32).reshape(cfg.n_regs,
                                                     cfg.reg_words)

    def run(self, state, program: Program):
        vrf, _, _ = self.sim.run_program(state, program)
        return vrf

    def scan_fn(self, sew: int):
        def run_one(vrf, arrays):
            out, _, _ = self.sim.run_trace(vrf, arrays, sew)
            return out
        return run_one


#: Execution backends implementing the Engine protocol.  "scan" is the
#: ``lax.scan`` reference interpreter; "pallas" is the fused TPU kernel
#: (``repro.nmc.pallas_engine``; interpret mode when no TPU is attached,
#: which only the CPU tests ask for).
BACKENDS = ("scan", "pallas")

_DEFAULT_ENGINES: dict[tuple[str, str], Engine] = {}


def resolve_backend(backend: str) -> str:
    """Map ``"auto"`` to the Pallas kernels on a TPU, scan elsewhere."""
    if backend == "auto":
        import jax
        return "pallas" if jax.default_backend() == "tpu" else "scan"
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown backend {backend!r}: valid backends are "
            f"{BACKENDS + ('auto',)}")
    return backend


def get_engine(name: str, backend: str = "scan") -> Engine:
    """Default (paper-configuration) engine instances, shared per process.

    ``backend`` selects the implementation: ``"scan"`` (reference
    interpreters), ``"pallas"`` (fused kernels), or ``"auto"``.
    """
    backend = resolve_backend(backend)
    key = (name, backend)
    if key not in _DEFAULT_ENGINES:
        if name not in ("caesar", "carus"):
            raise KeyError(name)
        if backend == "scan":
            cls = CaesarTile if name == "caesar" else CarusTile
        else:
            from repro.nmc.pallas_engine import (PallasCaesarEngine,
                                                 PallasCarusEngine)
            cls = PallasCaesarEngine if name == "caesar" \
                else PallasCarusEngine
        _DEFAULT_ENGINES[key] = cls()
    return _DEFAULT_ENGINES[key]


def implementations() -> tuple[tuple[str, str], ...]:
    """All registered ``(engine, backend)`` variants — the conformance
    matrix ``tests/test_engines.py`` sweeps."""
    return tuple((n, b) for n in ("caesar", "carus") for b in BACKENDS)
