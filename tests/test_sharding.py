"""Sharding rules + a real multi-device pjit run (subprocess with 8 host
devices so the main pytest process keeps its single-device view)."""
import json
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.models import model as M
from repro.models.sharding import ShardingRules


def _rules(arch, shape=(4, 4), axes=("data", "model")):
    cfg = get_config(arch)
    # AbstractMesh avoids touching devices
    mesh = jax.sharding.AbstractMesh(shape, axes)
    return cfg, ShardingRules(cfg, mesh)


def test_dense_param_specs():
    cfg, rules = _rules("deepseek-coder-33b")
    params = M.abstract_params(cfg)
    specs = rules.param_specs(params)
    g = specs["groups"][0]
    assert g["wq"] == P(None, ("data",), "model")       # stacked: (L, D, H*hd)
    assert g["w_down"] == P(None, "model", ("data",))
    assert specs["embed"] == P("model", ("data",))
    assert specs["lm_head"] == P(("data",), "model")


def test_moe_param_specs_expert_parallel():
    cfg, rules = _rules("qwen3-moe-235b-a22b")
    params = M.abstract_params(cfg)
    g = rules.param_specs(params)["groups"][0]
    assert g["w_gate"] == P(None, "model", ("data",), None)  # (L, E, D, F)
    assert g["w_down"] == P(None, "model", None, ("data",))
    assert g["router"] == P(None, ("data",), None)


def test_unshardable_heads_fall_back_to_replication():
    cfg, rules = _rules("gemma3-4b", shape=(2, 16))
    params = M.abstract_params(cfg)
    g = rules.param_specs(params)["groups"][0]
    # 8 q-heads % 16 != 0 -> attention weights not TP-sharded
    assert g["wq"] == P(None, ("data",), None)
    # but the MLP still is
    assert g["w_gate"] == P(None, ("data",), "model")


def test_cache_specs_seq_sharding():
    cfg, rules = _rules("internlm2-20b")
    cache = M.abstract_cache(cfg, 16, 1024)
    specs = rules.cache_specs(cache, 16)
    assert specs["groups"][0]["k"] == P(None, ("data",), ("model",), None, None)
    assert specs["groups"][0]["len"] == P(None)


def test_batch1_replicates_batch_axis():
    cfg, rules = _rules("rwkv6-7b")
    cache = M.abstract_cache(cfg, 1, 1024)
    specs = rules.cache_specs(cache, 1, shard_seq_over_data=True)
    assert specs["groups"][0]["state"] == P(None, None, "model", None, None)


SUBPROCESS_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.configs import get_smoke_config
    from repro.models.sharding import ShardingRules
    from repro.train import make_train_step, init_train_state
    from repro.optim import AdamWConfig
    from repro.data import SyntheticLM

    multi_pod = %(multi_pod)s
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    mesh = jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
    cfg = get_smoke_config("smollm-135m")
    rules = ShardingRules(cfg, mesh)
    params, opt = init_train_state(cfg, jax.random.PRNGKey(0))
    pspecs = rules.param_specs(params)
    ospecs = {"master": pspecs, "m": pspecs, "v": pspecs, "step": P()}
    ds = SyntheticLM(cfg.vocab_size, 32, 8, seed=0)
    b = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}
    bspecs = rules.batch_specs(b, 8)
    named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                   is_leaf=lambda x: isinstance(x, P))
    step = make_train_step(cfg, AdamWConfig())
    with mesh, rules.activation_ctx(8):
        jitted = jax.jit(step, in_shardings=(named(pspecs), named(ospecs), named(bspecs)))
        params = jax.device_put(params, named(pspecs))
        opt = jax.device_put(opt, named(ospecs))
        b = jax.device_put(b, named(bspecs))
        p2, o2, m = jitted(params, opt, b)
    print(json.dumps({"loss": float(m["loss"]), "devices": len(jax.devices())}))
""")


@pytest.mark.parametrize("multi_pod", [False, True])
def test_pjit_train_step_multidevice(multi_pod, tmp_path):
    prog = SUBPROCESS_PROG % {"multi_pod": multi_pod}
    out = subprocess.run(
        [sys.executable, "-c", prog],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path)},
        cwd=".",
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["devices"] == 8
    import numpy as np
    assert np.isfinite(res["loss"]) and 3 < res["loss"] < 8
