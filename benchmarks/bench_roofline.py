"""Roofline table: aggregates the dry-run JSONs (benchmarks/results/dryrun)
into the per-(arch x shape x mesh) three-term roofline with MODEL_FLOPS
ratios. Does NOT compile anything — run `python -m repro.launch.dryrun
--all [--multi-pod]` first (results are committed by that step)."""
from __future__ import annotations

import json
import math
import os

RESULTS = os.path.join(os.path.dirname(__file__), "results", "dryrun")

TOKENS = {
    "train_4k": 256 * 4096,
    "prefill_32k": 32 * 32768,
    "decode_32k": 128,       # one token per sequence
    "long_500k": 1,
}


def _model_flops(arch_name: str, shape: str) -> float:
    from repro.configs import get_config
    from repro.models.model import num_params, active_params

    cfg = get_config(arch_name)
    n = active_params(cfg) if cfg.num_experts else num_params(cfg)
    toks = TOKENS[shape]
    if shape in ("train_4k",):
        return 6.0 * n * toks
    return 2.0 * n * toks  # inference fwd only


def load_rows(mesh: str = "16x16"):
    rows = []
    if not os.path.isdir(RESULTS):
        return rows
    for fname in sorted(os.listdir(RESULTS)):
        if not fname.endswith(f"__{mesh}.json"):
            continue
        r = json.load(open(os.path.join(RESULTS, fname)))
        if r.get("status") != "ok":
            continue
        arch, shape = r["arch"], r["shape"]
        n_dev = r["devices"]
        hlo_flops_global = r["cost"]["flops"] * n_dev
        mf = _model_flops(arch, shape)
        rt = r["roofline"]
        rows.append({
            "arch": arch,
            "shape": shape,
            "mesh": mesh,
            "t_compute": rt["t_compute"],
            "t_memory": rt["t_memory"],
            "t_collective": rt["t_collective"],
            "bottleneck": rt["bottleneck"],
            "model_flops": mf,
            "hlo_flops_global": hlo_flops_global,
            "useful_ratio": mf / hlo_flops_global if hlo_flops_global else 0.0,
            "roofline_fraction": rt["roofline_fraction"],
            "attn_bytes_frac": None,
            "compile_s": r.get("compile_s"),
        })
    return rows


def run(fast: bool = True):
    out = []
    for mesh in ("16x16", "2x16x16"):
        for r in load_rows(mesh):
            name = f"roofline.{r['arch']}.{r['shape']}.{mesh}"
            t_star = max(r["t_compute"], r["t_memory"], r["t_collective"])
            out.append((
                name,
                t_star * 1e6,  # the modeled step time, us
                f"bottleneck={r['bottleneck']};tc={r['t_compute']:.3g};"
                f"tm={r['t_memory']:.3g};tx={r['t_collective']:.3g};"
                f"useful={r['useful_ratio']:.3f};roofline_frac={r['roofline_fraction']:.3f}",
            ))
    if not out:
        out.append(("roofline.missing", 0.0, "run repro.launch.dryrun first"))
    return out


if __name__ == "__main__":
    from repro.launch.cache import enable_persistent_cache

    enable_persistent_cache()
    for row in run():
        print(",".join(map(str, row)))
