"""Roofline HLO accounting: trip-count awareness, dot-FLOP reconstruction,
collective parsing — validated against analytic oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SHAPES, get_config
from repro.roofline.analysis import model_flops
from repro.roofline.hlo_parse import analyze


def _scan_model(n_layers, b=16, d=64):
    w = jnp.ones((n_layers, d, d), jnp.float32)

    def f(x):
        def body(h, wl):
            return jnp.tanh(h @ wl), None

        return jax.lax.scan(body, x, w)[0].sum()

    return jax.jit(f).lower(jnp.ones((b, d))).compile()


def _cost_flops(compiled) -> float:
    return compiled.cost_analysis()["flops"]


def test_cost_analysis_misses_scan_trips():
    """The motivating defect: XLA's cost_analysis counts loop bodies once."""
    f2 = _cost_flops(_scan_model(2))
    f8 = _cost_flops(_scan_model(8))
    assert f2 == f8  # identical despite 4x the work


@pytest.mark.parametrize("n_layers", [2, 8, 31])
def test_parser_flops_exact_for_scans(n_layers):
    b, d = 16, 64
    t = analyze(_scan_model(n_layers, b, d).as_text())
    assert t.dot_flops == pytest.approx(n_layers * 2 * b * d * d, rel=1e-6)


def test_parser_counts_nested_scans():
    w = jnp.ones((4, 3, 8, 8), jnp.float32)  # outer 4, inner 3

    def f(x):
        def outer(h, wl):
            def inner(hh, wm):
                return jnp.tanh(hh @ wm), None

            return jax.lax.scan(inner, h, wl)[0], None

        return jax.lax.scan(outer, x, w)[0].sum()

    t = analyze(jax.jit(f).lower(jnp.ones((4, 8))).compile().as_text())
    assert t.dot_flops == pytest.approx(4 * 3 * 2 * 4 * 8 * 8, rel=1e-6)


def test_parser_unrolled_matches_scanned():
    b, d, n = 8, 32, 5
    ws = [jnp.eye(d) for _ in range(n)]

    def unrolled(x):
        for w in ws:
            x = jnp.tanh(x @ w)
        return x.sum()

    t_unrolled = analyze(jax.jit(unrolled).lower(jnp.ones((b, d))).compile().as_text())
    t_scanned = analyze(_scan_model(n, b, d).as_text())
    assert t_unrolled.dot_flops == t_scanned.dot_flops


def test_collectives_parsed_on_sharded_compile():
    import os

    if jax.device_count() < 2:
        pytest.skip("needs multiple devices (runs under forced-device tests)")
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("data",))
    xs = jax.ShapeDtypeStruct((8, 16), jnp.float32,
                              sharding=NamedSharding(mesh, P("data")))

    def f(x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(None))
        ).sum()

    t = analyze(jax.jit(f).lower(xs).compile().as_text())
    assert t.collective_bytes > 0
    assert any("all-gather" in k for k in t.collective_ops)


def test_model_flops_conventions():
    cfg = get_config("deepseek_67b")
    n = cfg.active_param_count()
    assert model_flops(cfg, SHAPES["train_4k"]) == pytest.approx(
        6 * n * 256 * 4096
    )
    assert model_flops(cfg, SHAPES["prefill_32k"]) == pytest.approx(
        2 * n * 32 * 32768
    )
    assert model_flops(cfg, SHAPES["decode_32k"]) == pytest.approx(2 * n * 128)


def test_moe_uses_active_params():
    cfg = get_config("mixtral_8x7b")
    assert cfg.active_param_count() < cfg.param_count() * 0.35
    assert model_flops(cfg, SHAPES["train_4k"]) == pytest.approx(
        6 * cfg.active_param_count() * 256 * 4096
    )
