"""Serving engine: prefill + decode with donated KV caches and continuous
batching.

The near-memory contract at the serving level: caches are donated buffers
updated in place (memory-mode/compute-mode duality), and with
``nmc_mode='w8a8'`` every projection runs the quantized int8 path
(params converted once via ``quantize_params``).

``ServeEngine`` implements slot-based continuous batching: a fixed decode
batch of S slots; finished sequences free their slot, queued requests are
prefilled into it (prefill at batch 1 here; production would chunk).

All device work — prefill admission and decode steps — is dispatched as
queued work through an :class:`repro.nmc.DispatchQueue` from the curated
``repro.nmc`` public surface (with ``nmc_mode='w8a8'`` those are exactly
the int8 NMC projections): the queue launches the computations
asynchronously and the engine blocks only at future resolution, so a
batch of admissions issues all its prefills before the first slot
insert (DESIGN.md §5.2).  By default the engine joins the shared
:func:`repro.nmc.default_runtime` queue, so serving traffic and
``nmc.jit`` kernel calls drain through one dispatch discipline.

W8A8 projections offloaded to the simulated tile array
(:meth:`ServeEngine.nmc_project`) shard across ``nmc_tiles`` tiles via
the partitioning planner (DESIGN.md §9) — the same planner, queue and
bucketed jit cache every ``nmc.jit(tiles=N)`` kernel uses.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import nmc
from repro.models import layers as L
from repro.models import lm
from repro.models.config import ModelConfig
from repro.nmc import DispatchQueue


def quantize_params(params: dict, cfg: ModelConfig) -> dict:
    """Convert trained params to the NMC int8 serving form (DESIGN.md B)."""
    return L.quantize_tree(params)


def make_prefill_step(cfg: ModelConfig, max_len: int):
    def prefill_step(params, batch):
        return lm.prefill(params, batch, cfg, max_len)
    return prefill_step


def make_decode_step(cfg: ModelConfig):
    def decode_step(params, tokens, caches, cache_len):
        return lm.decode_step(params, tokens, caches, cache_len, cfg)
    return decode_step


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (S,) int32
    max_new: int = 16
    out: Optional[list] = None


class ServeEngine:
    """Slot-based continuous batching on a single host (tests/examples)."""

    def __init__(self, cfg: ModelConfig, params, n_slots: int = 4,
                 max_len: int = 256, greedy: bool = True,
                 nmc_queue: Optional[DispatchQueue] = None,
                 nmc_tiles: int = 1,
                 max_prefills: Optional[int] = None):
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        # admission control: at most this many prefills launch per step
        # (None = one per free slot), so at serving scale prefill waves
        # interleave with decode waves instead of stalling every active
        # slot behind a burst of arrivals
        if max_prefills is not None and max_prefills < 1:
            raise ValueError(
                f"max_prefills must be >= 1 or None, got {max_prefills!r}")
        self.max_prefills = max_prefills
        self.nmc_queue = nmc_queue if nmc_queue is not None \
            else nmc.default_runtime().queue
        # W8A8 projections offloaded to the NMC tile array shard across
        # this many tiles via the partitioning planner (DESIGN.md §9);
        # they dispatch through THIS engine's queue (for_queue wraps a
        # caller-owned queue as a kernel runtime), so serving traffic and
        # projection waves share one dispatch discipline and jit cache
        self.nmc_tiles = int(nmc_tiles)
        if self.nmc_tiles < 1:
            raise ValueError(f"nmc_tiles must be >= 1, got {nmc_tiles!r}")
        self._nmc_rt = nmc.NmcRuntime.for_queue(self.nmc_queue)
        self._nmc_proj: dict = {}       # (m, k, n, sew) -> CompiledKernel
        self.decode = jax.jit(make_decode_step(cfg), donate_argnums=(2,))
        self.prefill = jax.jit(make_prefill_step(cfg, max_len))
        self.caches = lm.init_caches(params, cfg, n_slots, max_len,
                                     dtype=cfg.dtype)
        # explicit per-leaf batch axes from the family that built the
        # cache — slot writes must not sniff axes from leaf shapes (a
        # size-1 layer axis is indistinguishable from a size-1 batch axis)
        self._cache_axes = lm.cache_batch_axes(cfg, self.caches)
        self._insert = jax.jit(_slot_writer(self._cache_axes),
                               donate_argnums=(0,))
        self.slot_req: list[Optional[Request]] = [None] * n_slots
        self.slot_len = np.zeros(n_slots, np.int32)
        self.slot_remaining = np.zeros(n_slots, np.int32)
        self.slot_last_tok = np.zeros(n_slots, np.int32)
        self.queue: list[Request] = []
        self.done: list[Request] = []
        # what each program of the last step routed to the held experts of
        # a served expert model: (rows in the call, rows routed to each
        # held expert per layer), left on the device; empty otherwise
        self.expert_rows: list[tuple[int, jax.Array]] = []

    # -- NMC tile-array offload ----------------------------------------------
    def nmc_project(self, x8, w8, sew: int = 8) -> np.ndarray:
        """One W8A8 projection ``y = x8 @ w8`` executed on the NMC tile
        array, sharded across ``nmc_tiles`` tiles by the partitioning
        planner (DESIGN.md §9): activation entries are scalar taps, weight
        rows are resident vectors, and the ``"axis"`` strategy gives each
        tile a contiguous column slice of every weight row (the same
        layout the resident-block path keeps on-array), the gather
        reassembling ``(m, n)``.  At the default ``sew=8``
        the result carries bit-exact int8 wrap-at-8 semantics (two's
        complement), matching the quantized kernels the Table V matmul
        models; ``sew=32`` widens the int8 operands into 32-bit lanes for
        exact int32 accumulation (the resident-block serving contract).

        This is the serving-level hook onto the paper's hardware path: the
        jitted bf16/int8 JAX decode loop stands in for the host CPU, and
        projections routed here run on the simulated tile array through
        the same planner and bucketed jit cache as ``nmc.jit`` kernels —
        submitted to *this engine's* dispatch queue (``nmc_queue``), so
        prefill/decode work and projection waves drain through one
        discipline.  Demo-scale by design — one projection per call,
        shapes bounded by a tile's SRAM macro."""
        x8 = np.asarray(x8, np.int8)
        w8 = np.asarray(w8, np.int8)
        m, k = x8.shape
        assert w8.shape[0] == k, (x8.shape, w8.shape)
        n = int(w8.shape[1])
        # keyed on the full shape (m, k, n) plus sew: two weights with the
        # same (m, k) but different output widths n must not share a cache
        # entry, and sew=32 callers (exact int32 accumulation for the
        # resident-block comparison path) must not collide with sew=8
        kern = self._nmc_proj.get((m, k, n, sew))
        if kern is None:
            def proj(t, X, W):
                a = t.consts(X)
                rows = [t.load(W[r]) for r in range(k)]
                for i in range(m):
                    acc = None
                    for kk in range(k):
                        acc = nmc.mac(acc, a[i, kk], rows[kk])
                    t.store(acc)
            # "axis" column-shards the weight loads (each tile holds its
            # slice of W, cpool replicated) — the layout wide projections
            # need to fit a tile's bank, and the one ResidentProjection
            # keeps on-array
            kern = nmc.jit(proj, sew=sew, tiles=self.nmc_tiles,
                           partition="axis", runtime=self._nmc_rt)
            self._nmc_proj[(m, k, n, sew)] = kern
        if sew == 8:
            return np.asarray(kern(x8, w8)).reshape(m, n)
        # widen int8 operands into sew-bit lanes: accumulation is exact
        # (k * 127^2 < 2^31 for any tile-resident k), true W8A8 GEMM
        return np.asarray(kern(x8.astype(np.int32),
                               w8.astype(np.int32))).reshape(m, n)

    def resident_block(self, layer: int = 0, rows: Optional[int] = None,
                       tiles: Optional[int] = None):
        """Build a :class:`repro.serve.block.ResidentBlock` over one decoder
        layer's weights: the whole W8A8 block (q/k/v/o projections + MLP)
        runs as chained partitioned waves on the tile array with the
        quantized weights resident — loaded once, reused every token; only
        activation words cross the bus per call (DESIGN.md §12).

        ``rows`` is the per-call token-row count (defaults to this engine's
        slot count); ``tiles`` the per-projection shard width (defaults to
        ``nmc_tiles``).  Dispatches through this engine's queue, so block
        waves and serving traffic share one discipline."""
        from repro.serve.block import ResidentBlock
        if self.cfg.family not in ("dense", "vlm"):
            raise ValueError(
                f"resident_block supports stacked dense decoder layers, "
                f"not family {self.cfg.family!r}")
        lp = jax.tree.map(lambda a: np.asarray(a[layer]),
                          self.params["layers"])
        return ResidentBlock(self.cfg, lp, queue=self.nmc_queue,
                             rows=rows if rows is not None else self.n_slots,
                             tiles=tiles if tiles is not None
                             else self.nmc_tiles)

    # -- admission -----------------------------------------------------------
    def submit(self, req: Request):
        req.out = []
        self.queue.append(req)

    def _admit(self):
        # two-phase admission: launch a prefill for every (free slot, queued
        # request) pair as queued device work first — the dispatch queue's
        # async launches overlap on the device — then resolve the futures
        # and write each cache into its slot.  Bit-identical to admitting
        # one slot at a time (prefills are independent); only the overlap
        # differs.
        launches = []
        for s in range(self.n_slots):
            if self.max_prefills is not None \
                    and len(launches) >= self.max_prefills:
                break
            if self.slot_req[s] is None and self.queue:
                req = self.queue.pop(0)
                fut = self.nmc_queue.submit_call(
                    self.prefill, self.params,
                    {"tokens": jnp.asarray(req.prompt[None])})
                launches.append((s, req, fut))
        for s, req, fut in launches:
            # .value, not .result(): the arrays are their own futures — the
            # argmax below forces logits while the slot insert stays queued
            logits, caches1 = fut.value
            if lm.EXPERT_ROWS in caches1:
                self.expert_rows.append((len(req.prompt),
                                         caches1[lm.EXPERT_ROWS]))
            # write the single-sequence cache into slot s in place (the
            # batched cache is donated), on the batch axis the cache family
            # declares for each leaf (never sniffed from leaf shapes)
            self.caches = self._insert(self.caches, caches1, s)
            tok = int(jnp.argmax(logits[0]))
            req.out.append(tok)
            self.slot_req[s] = req
            self.slot_len[s] = len(req.prompt) + 1
            self.slot_remaining[s] = req.max_new - 1
            self.slot_last_tok[s] = tok
            # prefill itself produced one token; a request exhausted by it
            # (max_new=1, or the prompt already fills max_len) retires here
            # instead of riding a decode step that would emit an extra token
            if self.slot_remaining[s] <= 0 or self.slot_len[s] >= self.max_len:
                self.done.append(req)
                self.slot_req[s] = None

    # -- decode loop ----------------------------------------------------------
    def step(self):
        self.expert_rows = []
        self._admit()
        active = [s for s in range(self.n_slots) if self.slot_req[s]]
        if not active:
            return False
        toks = jnp.asarray(self.slot_last_tok[:, None])
        clen = jnp.asarray(self.slot_len)
        # decode is queued NMC work too: launched async; only the sampled
        # tokens are forced below, the cache update stays in flight under
        # the host-side slot bookkeeping
        fut = self.nmc_queue.submit_call(self.decode, self.params, toks,
                                         self.caches, clen)
        logits, self.caches = fut.value
        if lm.EXPERT_ROWS in self.caches:
            self.expert_rows.append((self.n_slots,
                                     self.caches[lm.EXPERT_ROWS]))
        nxt = np.asarray(jnp.argmax(logits, -1))
        for s in active:
            req = self.slot_req[s]
            req.out.append(int(nxt[s]))
            self.slot_last_tok[s] = int(nxt[s])
            self.slot_len[s] += 1
            self.slot_remaining[s] -= 1
            if self.slot_remaining[s] <= 0 or self.slot_len[s] >= self.max_len:
                self.done.append(req)
                self.slot_req[s] = None
        return True

    def run(self, max_steps: int = 1000):
        steps = 0
        while (self.queue or any(self.slot_req)) and steps < max_steps:
            self.step()
            steps += 1
        return self.done


def _slot_writer(axes):
    """``(caches, one, s) -> caches`` with the batch-1 cache tree ``one``
    written into slot ``s`` of every leaf, on the leaf's batch axis."""
    def insert(caches, one, s):
        return jax.tree.map(lambda full, o, ax: _insert_slot(full, o, s, ax),
                            caches, one, axes)
    return insert


def _insert_slot(full, one, s, axis: int):
    """Write a batch-1 cache entry into slot s of the batched cache along
    the explicit ``axis`` declared by :func:`repro.models.lm.cache_batch_axes`
    (shape sniffing misreads single-layer stacks, whose layer dim of 1 is
    indistinguishable from a batch dim of 1).  An entry that holds no
    per-slot state (``lm.NOT_PER_SLOT``) is passed by."""
    if axis == lm.NOT_PER_SLOT:
        return full
    return jax.lax.dynamic_update_slice_in_dim(full, one.astype(full.dtype),
                                               s, axis=axis)
