"""Trip-count-aware HLO cost model: known-workload validation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_cost import HloCostModel, module_cost
from repro.launch.hlo_stats import CHIPS, roofline_terms


def test_scan_matmul_flops():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    x = jax.ShapeDtypeStruct((128, 256), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((256, 256), jnp.bfloat16)
    c = jax.jit(f).lower(x, w).compile()
    mc = module_cost(c.as_text())
    want = 2 * 128 * 256 * 256 * 10
    assert want <= mc["flops"] <= 1.1 * want


def test_nested_scan_flops():
    def f(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            c, _ = jax.lax.scan(inner, c, None, length=4)
            return c, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    c = jax.jit(f).lower(x, w).compile()
    mc = module_cost(c.as_text())
    want = 2 * 64 * 64 * 64 * 12  # 3 * 4 iterations
    assert want <= mc["flops"] <= 1.15 * want


def test_batched_dot_flops():
    def f(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)

    a = jax.ShapeDtypeStruct((8, 32, 64), jnp.float32)
    b = jax.ShapeDtypeStruct((8, 64, 16), jnp.float32)
    c = jax.jit(f).lower(a, b).compile()
    mc = module_cost(c.as_text())
    want = 2 * 8 * 32 * 64 * 16
    assert want <= mc["flops"] <= 1.1 * want


def test_roofline_terms_bottleneck():
    t = roofline_terms("TPU v5e", 1e15, 1e9, 1e9)
    assert t["bottleneck"] == "compute"
    assert t["t_compute"] == pytest.approx(
        1e15 / CHIPS["TPU v5e"]["peak_flops"])
    assert CHIPS["TPU v5e"]["peak_flops"] == 197e12
    assert CHIPS["TPU v5e"]["hbm_bw"] == 819e9
    t = roofline_terms("TPU v5e", 1e9, 1e13, 1e9)
    assert t["bottleneck"] == "memory"
    t = roofline_terms("TPU v5e", 1e9, 1e9, 1e12)
    assert t["bottleneck"] == "collective"


def test_roofline_terms_unknown_chip_raises():
    # JAX's device_kind for a v5e shares the published row
    assert roofline_terms("TPU v5 lite", 1e15, 1e9, 1e9) == roofline_terms(
        "TPU v5e", 1e15, 1e9, 1e9)
    with pytest.raises(ValueError, match="no peak figures"):
        roofline_terms("cpu", 1e15, 1e9, 1e9)


def test_shape_parsing_tuples():
    m = HloCostModel(
        "ENTRY %main (p: f32[4]) -> f32[4] {\n"
        "  %t = (f32[128,256]{1,0}, s32[], /*index=2*/bf16[64]{0}) tuple(%a, %b, %c)\n"
        "}\n"
    )
    op = m.computations["main"][0]
    assert op["opcode"] == "tuple"
    assert op["bytes"] == 128 * 256 * 4 + 4 + 64 * 2
