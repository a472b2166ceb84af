"""Roofline analysis over the dry-run artifacts (§Roofline deliverable).

Per (arch x shape x mesh) cell, derive the three roofline terms from the
compiled dry-run (all per-device quantities from the trip-count-expanded HLO
analysis in repro.launch.hlo_analysis):

  compute    = HLO_FLOPs / peak_FLOPs            (197 TF/s bf16; 394 int8)
  memory     = HLO_bytes / HBM_bw                (819 GB/s)
  collective = collective_link_bytes / link_bw   (50 GB/s per chip)

Two memory variants are reported:
  * raw          — the compiled XLA program as-is (includes the S^2 score
                   traffic of the chunked-attention XLA fallback),
  * tpu-kernel   — attention-fallback traffic (named_scope-attributed)
                   replaced by the Pallas flash kernel's Q/K/V/O streaming
                   I/O (computed analytically; the kernel keeps scores and
                   softmax stats in VMEM).

MODEL_FLOPS = 6·N·D (train) / 2·N·D (serve), N = active params, D = tokens.
Also reported: MODEL/HLO ratio (useful-compute fraction; catches remat and
dispatch waste) and the roofline-limited MFU bound.
"""

from __future__ import annotations

import glob
import json
import os

from repro.configs import SHAPES, get
from repro.core.constants import TPU_V5E


def flash_io_bytes(arch: str, shape_name: str) -> float:
    """Analytic HBM traffic of the Pallas flash-attention kernel for every
    attention site in one step (GLOBAL bytes; divide by devices)."""
    cfg = get(arch)
    shape = SHAPES[shape_name]
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "decode":
        return 0.0   # decode path reads the cache directly (no fallback)
    hd = cfg.head_dim
    bytes_per = 2  # bf16

    def site(sq, skv, hq, hkv, dv=None):
        dv = dv or hd
        q = b * sq * hq * hd * bytes_per
        k = b * skv * hkv * hd * bytes_per
        v = b * skv * hkv * dv * bytes_per
        o = b * sq * hq * dv * bytes_per
        return q + k + v + o

    if cfg.family == "encdec":
        per_pass = (cfg.n_enc_layers * site(cfg.enc_seq, cfg.enc_seq,
                                            cfg.n_heads, cfg.n_kv_heads)
                    + cfg.n_layers * (site(s, s, cfg.n_heads, cfg.n_kv_heads)
                                      + site(s, cfg.enc_seq, cfg.n_heads,
                                             cfg.n_kv_heads)))
    elif cfg.family == "hybrid":
        n_attn = cfg.n_layers // cfg.attn_every
        per_pass = n_attn * site(s, s, cfg.n_heads, cfg.n_kv_heads)
    elif cfg.family == "xlstm":
        per_pass = 0.0
    elif cfg.mla:
        dq = cfg.qk_nope_dim + cfg.qk_rope_dim
        per_pass = cfg.n_layers * (
            b * s * cfg.n_heads * dq * bytes_per * 2      # q + k
            + b * s * cfg.n_heads * cfg.v_head_dim * bytes_per * 2)  # v + o
    else:
        per_pass = cfg.n_layers * site(s, s, cfg.n_heads, cfg.n_kv_heads)
    # fwd = 1 pass; train adds remat-fwd + bwd (dq,dk,dv + reread) ~ 3 more
    passes = 4.0 if shape.kind == "train" else 1.0
    return per_pass * passes


def load_cells(path: str = "results/dryrun") -> list[dict]:
    cells = []
    for f in sorted(glob.glob(os.path.join(path, "*.json"))):
        with open(f) as fh:
            cells.append(json.load(fh))
    return cells


def roofline_row(rec: dict, spec=TPU_V5E) -> dict:
    n = rec["n_devices"]
    flops = rec["hlo"]["flops"]
    peak = spec.peak_int8_ops if rec.get("nmc_mode") == "w8a8" \
        else spec.peak_bf16_flops
    raw_bytes = rec["hlo"]["hbm_bytes"]
    attn_fb = rec["hlo"].get("attn_fallback_bytes", 0.0)
    fio = flash_io_bytes(rec["arch"], rec["shape"]) / n
    adj_bytes = max(raw_bytes - attn_fb, 0.0) + fio

    t_comp = flops / peak
    t_mem_raw = raw_bytes / spec.hbm_bw
    t_mem = adj_bytes / spec.hbm_bw
    t_coll = rec["hlo"]["collective_link_bytes"] / spec.ici_link_bw

    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    est = max(terms.values())

    kind = rec["kind"]
    n_active = rec["active_params"]
    model_flops = (6 if kind == "train" else 2) * n_active * rec["tokens"]
    hlo_global = flops * n
    mfu_bound = (model_flops / (n * spec.peak_bf16_flops)) / est if est else 0
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "nmc": rec.get("nmc_mode", "none"), "tag": rec.get("tag", ""),
        "t_compute_s": t_comp, "t_memory_s": t_mem,
        "t_memory_raw_s": t_mem_raw, "t_collective_s": t_coll,
        "dominant": dominant, "est_step_s": est,
        "model_flops": model_flops, "hlo_flops_global": hlo_global,
        "useful_ratio": model_flops / hlo_global if hlo_global else 0.0,
        "mfu_bound": mfu_bound,
        "peak_hbm_gib": rec["memory"]["peak_bytes"] / 2**30,
        "compile_s": rec["compile_s"],
    }


def dominant_note(row: dict) -> str:
    d = row["dominant"]
    if d == "compute":
        return "compute-bound: raise useful-flop fraction (remat policy) " \
               "or drop to int8 NMC mode"
    if d == "memory":
        return "memory-bound: fuse attention (Pallas), recompute masks, " \
               "cast residuals bf16"
    return "collective-bound: shrink TP degree / overlap collectives " \
           "with compute"


def main(path: str = "results/dryrun", out_csv: str | None = None):
    rows = [roofline_row(r) for r in load_cells(path)
            if not r.get("tag") and r.get("nmc_mode", "none") == "none"]
    rows.sort(key=lambda r: (r["arch"], r["shape"], r["mesh"]))
    hdr = (f"{'arch':22s} {'shape':12s} {'mesh':8s} {'comp_s':>9s} "
           f"{'mem_s':>9s} {'coll_s':>9s} {'dom':>10s} {'useful':>7s} "
           f"{'MFUbound':>8s} {'peakGiB':>8s}")
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        print(f"{r['arch']:22s} {r['shape']:12s} {r['mesh']:8s} "
              f"{r['t_compute_s']:9.4f} {r['t_memory_s']:9.4f} "
              f"{r['t_collective_s']:9.4f} {r['dominant']:>10s} "
              f"{r['useful_ratio']:7.3f} {r['mfu_bound']:8.3f} "
              f"{r['peak_hbm_gib']:8.2f}")
    if out_csv:
        import csv
        with open(out_csv, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=list(rows[0].keys()))
            w.writeheader()
            w.writerows(rows)
        print(f"\nwrote {out_csv} ({len(rows)} rows)")
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    import sys
    main(out_csv=sys.argv[1] if len(sys.argv) > 1 else
         "results/roofline.csv")
