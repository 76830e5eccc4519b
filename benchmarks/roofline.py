"""§Roofline: three-term analysis of every dry-run cell (deliverable g).

Reads experiments/dryrun/*.json (produced by repro.launch.dryrun) and emits,
per (arch × shape × mesh):

  compute_s    = per-device HLO flops / peak
  memory_s     = per-device HLO bytes / HBM bw  (CPU backend legalises bf16
                 compute to f32 — the bf16_corrected column halves byte terms
                 for bf16 programs; both are reported)
  collective_s = per-device collective send bytes / ICI bw
  dominant term, MODEL_FLOPS / (HLO flops × chips) useful-compute ratio,
  and the roofline fraction  (model-flop time / dominant-term time).

Also writes the markdown table consumed by EXPERIMENTS.md §Roofline.
"""

from __future__ import annotations

import glob
import json
import os

from benchmarks.common import HBM_BW, ICI_BW, PEAK_FLOPS, csv

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                          "dryrun")
OUT_MD = os.path.join(os.path.dirname(__file__), "..", "experiments",
                      "roofline.md")


def analyse(rec: dict) -> dict:
    chips = rec["chips"]
    flops_dev = rec["hlo_flops"]             # per-device (SPMD module)
    bytes_dev = rec["hlo_bytes"]
    coll_dev = rec["collective_bytes"]
    compute_s = flops_dev / PEAK_FLOPS
    memory_s = bytes_dev / HBM_BW
    memory_s_bf16 = memory_s / 2             # CPU f32-legalisation correction
    collective_s = coll_dev / ICI_BW
    terms = {"compute": compute_s, "memory": memory_s_bf16,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    model = rec.get("model_flops", 0.0)
    if "useful_bytes" in rec:    # memory-bound cells: bandwidth utilisation
        # bf16 LM cells keep the legalisation correction; f32 solver cells
        # don't need one
        mem_term = memory_s_bf16 if rec.get("bf16") else memory_s
        eff_bytes = bytes_dev / 2 if rec.get("bf16") else bytes_dev
        useful = rec["useful_bytes"] / (eff_bytes * chips) if bytes_dev else 0.0
        model_time = rec["useful_bytes"] / chips / HBM_BW
        terms["memory"] = mem_term
        dominant = max(terms, key=terms.get)
    else:
        useful = model / (flops_dev * chips) if flops_dev else 0.0
        model_time = model / chips / PEAK_FLOPS
    roofline_fraction = model_time / max(terms.values()) if max(
        terms.values()) else 0.0
    return dict(
        compute_s=compute_s, memory_s=memory_s, memory_s_bf16=memory_s_bf16,
        collective_s=collective_s, dominant=dominant, useful_ratio=useful,
        roofline_fraction=roofline_fraction,
    )


def main() -> None:
    rows = []
    for path in sorted(glob.glob(os.path.join(DRYRUN_DIR, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        if rec.get("kind") == "decode":
            # decode is memory-bound: useful traffic = active params + the
            # KV/SSM cache slab read once per token
            from repro.configs.base import get_config
            cfg = get_config(rec["arch"])
            S, B = rec["seq_len"], rec["batch"]
            KV, hd, L = cfg.n_kv_heads, cfg.hd, cfg.n_layers
            cache = 0.0
            if cfg.has_attn:
                C = min(S, cfg.sliding_window) if cfg.sliding_window else S
                cache = 2.0 * B * C * KV * hd * 2 * L
                if cfg.local_global:   # half the layers use the window
                    Cw = min(S, cfg.sliding_window)
                    cache = (B * Cw * KV * hd + B * S * KV * hd) * 2 * L
            if cfg.has_ssm:
                cache += (B * cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state
                          * 4 * L)
            rec = dict(rec)
            rec["useful_bytes"] = 2.0 * cfg.active_param_count() + cache
            rec["bf16"] = True
        if "arch" not in rec:        # solver cells: memory-bound accounting
            from repro.core.operators import touched_elements_per_iter
            a = dict(rec)
            a["arch"] = f"hpcg-{rec['method']}-{rec['stencil']}"
            a["shape"] = "weak_128^3"
            nbar = 7 if rec["stencil"] == "7pt" else 27
            r_global = 1
            for d in rec["global_grid"]:
                r_global *= d
            touched = touched_elements_per_iter(rec["method"], nbar)
            # solvers are memory-bound: "useful flops" ~ 2 flops/element;
            # the meaningful roofline number is bandwidth utilisation
            # (useful bytes / HLO bytes) — recorded in useful_ratio below.
            a["model_flops"] = 2.0 * touched * r_global
            a["useful_bytes"] = 4.0 * touched * r_global   # f32 cells
            rec = a
        r = analyse(rec)
        tag = f"{rec['arch']}|{rec['shape']}|{rec['mesh']}"
        rows.append((tag, rec, r))
        csv(f"roofline_{tag}", max(r['compute_s'], r['memory_s_bf16'],
                                   r['collective_s']) * 1e6,
            f"dom={r['dominant']};frac={r['roofline_fraction']:.3f};"
            f"useful={r['useful_ratio']:.2f};"
            f"c={r['compute_s']*1e3:.2f}ms;m={r['memory_s_bf16']*1e3:.2f}ms;"
            f"x={r['collective_s']*1e3:.2f}ms")

    os.makedirs(os.path.dirname(OUT_MD), exist_ok=True)
    with open(OUT_MD, "w") as f:
        f.write("| cell | mesh | compute_s | memory_s(bf16) | collective_s |"
                " dominant | useful | roofline frac |\n|---|---|---|---|---|"
                "---|---|---|\n")
        for tag, rec, r in rows:
            arch, shape, mesh = tag.split("|")
            f.write(
                f"| {arch} × {shape} | {mesh} | {r['compute_s']:.2e} |"
                f" {r['memory_s_bf16']:.2e} | {r['collective_s']:.2e} |"
                f" {r['dominant']} | {r['useful_ratio']:.2f} |"
                f" {r['roofline_fraction']:.3f} |\n")
    print(f"# wrote {OUT_MD} ({len(rows)} cells)")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
