"""Validate the loop-aware HLO cost analyzer against analytic expectations."""

import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlo_cost


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_scan_dot_flops_multiplied_by_trip_count():
    L, M, K, N = 10, 128, 256, 256

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        c, _ = jax.lax.scan(body, x, None, length=L)
        return c

    txt = _compiled_text(f, jax.ShapeDtypeStruct((M, K), jnp.float32),
                         jax.ShapeDtypeStruct((K, N), jnp.float32))
    got = hlo_cost.analyze(txt)
    want_flops = L * 2 * M * K * N
    assert got["flops"] == pytest.approx(want_flops, rel=0.01), got
    assert got["unknown_trip_loops"] == 0
    # traffic: at least L * (read c + w + write c) for the dot operands
    assert got["traffic_bytes"] >= L * (M * K + K * N + M * N) * 4


def test_single_dot_flops_exact():
    M, K, N = 64, 32, 48

    def f(a, b):
        return a @ b

    txt = _compiled_text(f, jax.ShapeDtypeStruct((M, K), jnp.float32),
                         jax.ShapeDtypeStruct((K, N), jnp.float32))
    got = hlo_cost.analyze(txt)
    assert got["flops"] == pytest.approx(2 * M * K * N, rel=0.01)


def test_batched_dot_flops():
    B, M, K, N = 4, 16, 32, 24

    def f(a, b):
        return jnp.einsum("bmk,bkn->bmn", a, b)

    txt = _compiled_text(f, jax.ShapeDtypeStruct((B, M, K), jnp.float32),
                         jax.ShapeDtypeStruct((B, K, N), jnp.float32))
    got = hlo_cost.analyze(txt)
    assert got["flops"] == pytest.approx(2 * B * M * K * N, rel=0.01)


def test_nested_scan_multiplies_both_trip_counts():
    L1, L2, M = 5, 7, 64

    def f(x, w):
        def inner(c, _):
            return jnp.tanh(c @ w), None

        def outer(c, _):
            c, _ = jax.lax.scan(inner, c, None, length=L2)
            return c, None
        c, _ = jax.lax.scan(outer, x, None, length=L1)
        return c

    txt = _compiled_text(f, jax.ShapeDtypeStruct((M, M), jnp.float32),
                         jax.ShapeDtypeStruct((M, M), jnp.float32))
    got = hlo_cost.analyze(txt)
    assert got["flops"] == pytest.approx(L1 * L2 * 2 * M * M * M, rel=0.01)


def test_collectives_counted_with_trip_multiplier():
    # 8 fake devices via a sub-mesh of the CPU host platform
    if jax.device_count() < 2:
        pytest.skip("needs >1 device (run under forced host device count)")


def test_xla_cost_analysis_undercounts_loops_demo():
    """Documents the bug this module works around."""
    def f(x, w):
        def body(c, _):
            return c @ w, None
        c, _ = jax.lax.scan(body, x, None, length=10)
        return c

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    compiled = jax.jit(f).lower(x, w).compile()
    ca = compiled.cost_analysis()
    xla_flops = ca["flops"]
    ours = hlo_cost.analyze(compiled.as_text())["flops"]
    assert ours == pytest.approx(10 * xla_flops, rel=0.05)
