import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract memory / cost / collective statistics.

Run:  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m \
          --shape train_4k [--multi-pod] [--all] [--out results.json]

The XLA_FLAGS line above MUST precede any jax import (device count locks
on first init).  Only this entrypoint sees 512 placeholder devices.
"""
import argparse
import json
import math
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.launch import hlo_stats
from repro.launch.mesh import make_production_mesh, data_axes
from repro.launch.shapes import SHAPES, all_cells, applicable, input_specs
from repro.models import model as M
from repro.models.sharding import ShardingRules
from repro.optim import AdamWConfig, init_opt_state
from repro.train.train_step import make_train_step

# the production meshes are 16x16 pods of this chip (hlo_stats.CHIPS)
CHIP = "TPU v5e"

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")


def _named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def _spec_bytes_per_device(abstract, specs, mesh) -> float:
    """Input bytes per device implied by the shardings (fallback when
    memory_analysis is unavailable on this backend)."""
    total = 0.0
    for leaf, spec in zip(jax.tree.leaves(abstract),
                          jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))):
        n = math.prod(leaf.shape) * leaf.dtype.itemsize if leaf.shape else leaf.dtype.itemsize
        shards = 1
        for ax in spec:
            if ax is None:
                continue
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                shards *= mesh.shape[a]
        total += n / shards
    return total


def choose_accum(cfg, sc, rules, budget_bytes: float = 4e9) -> int:
    """Microbatch count so remat-saved activations (one (B_l, S, D) bf16
    residual per layer per microbatch) fit the HBM budget. 50 GB of saved
    activations at accum=1 on the 235B cell would be 3x HBM by itself."""
    b_local = max(1, sc.global_batch // max(rules.n_data, 1))
    saved = cfg.num_layers * b_local * sc.seq_len * cfg.d_model * 2
    accum = 1
    while accum < b_local and saved / accum > budget_bytes:
        accum *= 2
    return accum


def build_cell(cfg, shape_name, mesh):
    """Returns (fn, args_abstract, in_shardings, out_shardings, donate)."""
    rules = ShardingRules(cfg, mesh)
    sc = SHAPES[shape_name]
    spec = input_specs(cfg, shape_name)
    params_a = M.abstract_params(cfg)
    pspecs = rules.param_specs(params_a)

    if sc.kind == "train":
        opt_a = jax.eval_shape(init_opt_state, params_a)
        ospecs = {
            "master": pspecs, "m": pspecs, "v": pspecs, "step": P(),
        }
        bspecs = rules.batch_specs(spec["batch"], sc.global_batch)
        accum = choose_accum(cfg, sc, rules)
        fn = make_train_step(cfg, AdamWConfig(), accum=accum, remat=True)
        args = (params_a, opt_a, spec["batch"])
        in_sh = (pspecs, ospecs, bspecs)
        metrics_sh = {"loss": P(), "grad_norm": P(), "lr_scale": P(), "step": P()}
        out_sh = (pspecs, ospecs, metrics_sh)
        donate = (0, 1)
        return fn, args, in_sh, out_sh, donate, sc.global_batch // accum

    if sc.kind == "prefill":  # noqa: placeholder keeps diff small
        bspecs = rules.batch_specs(spec["batch"], sc.global_batch)
        cache_a = M.abstract_cache(cfg, sc.global_batch, sc.seq_len)
        cspecs = rules.cache_specs(cache_a, sc.global_batch,
                                   shard_seq_over_data=(sc.global_batch == 1))
        logits_spec = P(rules.data_axes if sc.global_batch % rules.n_data == 0 else None,
                        "model" if cfg.vocab_size % rules.n_model == 0 else None)

        def fn(params, batch):
            return M.prefill(cfg, params, batch)

        args = (params_a, spec["batch"])
        in_sh = (pspecs, bspecs)
        cache_out = dict(cspecs)
        cache_out["len"] = P()
        out_sh = (logits_spec, cache_out)
        return fn, args, in_sh, out_sh, (), sc.global_batch

    # decode
    B, S = sc.global_batch, sc.seq_len
    cache_a = M.abstract_cache(cfg, B, S)
    # "one new token with a KV cache of seq_len": len = S-1 used slots
    bspecs = rules.batch_specs(spec["batch"], B)
    cspecs = rules.cache_specs(cache_a, B, shard_seq_over_data=(B == 1))
    cache_in = dict(cspecs)
    cache_in["len"] = P()
    logits_spec = P(rules.data_axes if B % rules.n_data == 0 else None,
                    "model" if cfg.vocab_size % rules.n_model == 0 else None)

    def fn(params, cache, batch):
        return M.decode_step(cfg, params, cache, batch)

    args = (params_a, cache_a, spec["batch"])
    in_sh = (pspecs, cache_in, bspecs)
    out_sh = (logits_spec, cache_in)
    donate = (1,)
    return fn, args, in_sh, out_sh, donate, sc.global_batch


def run_cell(arch: str, shape_name: str, multi_pod: bool, save: bool = True) -> dict:
    cfg = get_config(arch)
    ok, why = applicable(cfg.name, shape_name)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    row = {"arch": cfg.name, "shape": shape_name, "mesh": mesh_name}
    if not ok:
        row["status"] = f"skipped: {why}"
        return row
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    fn, args, in_sh, out_sh, donate, ctx_batch = build_cell(cfg, shape_name, mesh)
    rules = ShardingRules(cfg, mesh)
    sc = SHAPES[shape_name]
    with mesh, rules.activation_ctx(ctx_batch, seq_len=sc.seq_len):
        jitted = jax.jit(
            fn,
            in_shardings=_named(mesh, in_sh),
            out_shardings=_named(mesh, out_sh),
            donate_argnums=donate,
        )
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    n_dev = math.prod(mesh.shape.values())
    # ---- memory
    mem = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = {
                "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
                "output_bytes": getattr(ma, "output_size_in_bytes", None),
                "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
                "peak_bytes": getattr(ma, "peak_memory_in_bytes", None),
            }
    except Exception as e:  # CPU backend may not implement it
        mem = {"error": str(e)}
    arg_bytes_est = _spec_bytes_per_device(args, in_sh, mesh)

    # ---- cost: trip-count-aware HLO cost model (XLA's cost_analysis counts
    # while bodies once; see hlo_cost.py).  All values are per device.
    from repro.launch.hlo_cost import module_cost

    hlo = compiled.as_text()
    mc = module_cost(hlo)
    cost = {"flops": mc["flops"], "bytes accessed": mc["bytes"],
            "attn_bytes": mc["attn_bytes"]}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        cost["xla_flops_one_iter"] = float(ca.get("flops", 0.0))
    except Exception:
        pass

    coll = {
        "total_bytes": mc["coll_bytes"],
        "breakdown": {k: v for k, v in mc["coll_breakdown"].items() if v},
        "counts": {k: v for k, v in mc["coll_counts"].items() if v},
    }

    flops_dev = cost.get("flops", 0.0)
    bytes_dev = cost.get("bytes accessed", 0.0)
    terms = hlo_stats.roofline_terms(CHIP, flops_dev, bytes_dev,
                                     coll["total_bytes"])

    row.update({
        "status": "ok",
        "devices": n_dev,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory_analysis": mem,
        "input_bytes_per_dev_est": arg_bytes_est,
        "cost": cost,
        "collectives": coll,
        "roofline": terms,
        "num_params": None,   # filled by benchmarks (host-side count)
    })
    if save:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        fname = f"{cfg.name}__{shape_name}__{mesh_name}.json".replace("/", "_")
        with open(os.path.join(RESULTS_DIR, fname), "w") as f:
            json.dump(row, f, indent=1)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true", help="sweep all cells")
    ap.add_argument("--skip-done", action="store_true")
    args = ap.parse_args()

    if args.all:
        cells = all_cells()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    for arch, shape in cells:
        mesh_name = "2x16x16" if args.multi_pod else "16x16"
        cfgname = get_config(arch).name
        fname = os.path.join(RESULTS_DIR, f"{cfgname}__{shape}__{mesh_name}.json")
        if args.skip_done and os.path.exists(fname):
            print(f"[dryrun] {arch} {shape} {mesh_name}: cached, skipping")
            continue
        try:
            row = run_cell(arch, shape, args.multi_pod)
            r = row.get("roofline", {})
            print(
                f"[dryrun] {row['arch']:22s} {shape:12s} {mesh_name:8s} "
                f"{row['status']:4s} compile={row.get('compile_s', 0):6.1f}s "
                f"flops/dev={row.get('cost', {}).get('flops', 0):.3e} "
                f"coll={row.get('collectives', {}).get('total_bytes', 0):.3e}B "
                f"bottleneck={r.get('bottleneck', '-')}"
            )
        except Exception:
            print(f"[dryrun] {arch} {shape} {mesh_name}: FAILED")
            traceback.print_exc()


if __name__ == "__main__":
    main()
