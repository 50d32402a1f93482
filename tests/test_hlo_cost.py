"""Loop-aware HLO cost model: validate against XLA cost_analysis and
analytic flop counts on known programs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_cost import analyze_hlo


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_matmul_flops_exact():
    a = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    c = _compile(lambda x, y: x @ y, a, b)
    got = analyze_hlo(c.as_text())
    want = 2 * 128 * 256 * 512
    assert got["flops"] == pytest.approx(want, rel=0.05), got["flops"]
    # agrees with XLA on a loop-free program
    xla = c.cost_analysis()["flops"]
    assert got["flops"] == pytest.approx(xla, rel=0.05)


def test_scan_multiplies_trip_count():
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 64), jnp.float32)

    def loop(w, x, n):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=n)
        return y

    c8 = _compile(lambda w, x: loop(w, x, 8), w, x)
    c16 = _compile(lambda w, x: loop(w, x, 16), w, x)
    f8 = analyze_hlo(c8.as_text())["flops"]
    f16 = analyze_hlo(c16.as_text())["flops"]
    assert f16 == pytest.approx(2 * f8, rel=0.05), (f8, f16)
    # and the absolute count is ~ n * matmul flops
    want = 8 * 2 * 8 * 64 * 64
    assert f8 == pytest.approx(want, rel=0.3), (f8, want)


def test_bytes_scale_with_loop():
    x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)

    def loop(x, n):
        def body(c, _):
            return c * 2.0 + 1.0, None
        y, _ = jax.lax.scan(body, x, None, length=n)
        return y

    b4 = analyze_hlo(_compile(lambda x: loop(x, 4), x).as_text())["bytes"]
    b8 = analyze_hlo(_compile(lambda x: loop(x, 8), x).as_text())["bytes"]
    assert b8 > 1.5 * b4, (b4, b8)


def test_layers_scale_in_model_flops():
    """The regression this module exists for: flops must scale with layers."""
    import dataclasses
    from repro.models import transformer as tf

    base = tf.LMConfig(n_layers=2, d_model=64, n_heads=2, n_kv_heads=1,
                       head_dim=32, d_ff=128, vocab=128, remat=True,
                       dtype="float32", attn_chunk=32)
    flops = {}
    for L in (2, 4):
        cfg = dataclasses.replace(base, n_layers=L)
        p = jax.eval_shape(lambda: tf.init_params(cfg, jax.random.key(0)))
        b = {"tokens": jax.ShapeDtypeStruct((2, 64), jnp.int32),
             "labels": jax.ShapeDtypeStruct((2, 64), jnp.int32)}
        def grad(pp, bb, cfg=cfg):
            g = jax.grad(lambda q: tf.loss_fn(cfg, q, bb)[0])(pp)
            return jax.tree.map(lambda t: jnp.sum(t.astype(jnp.float32)), g)
        c = _compile(grad, p, b)
        flops[L] = analyze_hlo(c.as_text())["flops"]
        assert flops[L] != pytest.approx(c.cost_analysis()["flops"]) or L == 2
    ratio = flops[4] / flops[2]
    assert 1.3 < ratio < 2.2, flops
