"""Trip-count-aware HLO cost analyzer: validated against unrolled ground
truth (the property the XLA built-in breaks on)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_cost import analyze, breakdown

M = 256


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_scan_flops_match_unrolled():
    W = jax.ShapeDtypeStruct((8, M, M), jnp.float32)
    x = jax.ShapeDtypeStruct((M, M), jnp.float32)

    def scanned(w, x):
        return jax.lax.scan(lambda h, wi: (h @ wi, None), x, w)[0]

    def unrolled(w, x):
        for i in range(8):
            x = x @ w[i]
        return x

    t_scan = analyze(_compile(scanned, W, x).as_text())
    t_unroll = analyze(_compile(unrolled, W, x).as_text())
    expect = 8 * 2 * M ** 3
    assert t_scan.flops == pytest.approx(expect, rel=0.01)
    assert t_unroll.flops == pytest.approx(expect, rel=0.01)
    assert t_scan.while_trips == [8]


def test_grad_of_scan_counts_backward():
    W = jax.ShapeDtypeStruct((4, M, M), jnp.float32)
    x = jax.ShapeDtypeStruct((M, M), jnp.float32)

    def loss(w, x):
        return jax.lax.scan(lambda h, wi: (h @ wi, None), x, w)[0].sum()

    t = analyze(_compile(jax.grad(loss), W, x).as_text())
    # fwd (1 dot) + bwd (2 dots) per step
    expect = 3 * 4 * 2 * M ** 3
    assert t.flops == pytest.approx(expect, rel=0.05)


def test_dot_general_batched_flops():
    a = jax.ShapeDtypeStruct((4, 32, 64), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 64, 16), jnp.float32)
    t = analyze(_compile(lambda a, b: jnp.einsum("bij,bjk->bik", a, b),
                         a, b).as_text())
    assert t.flops == pytest.approx(2 * 4 * 32 * 64 * 16, rel=0.01)


def test_dus_in_loop_not_charged_full_buffer():
    """Stacking one row per iteration must cost O(row) per iteration,
    not O(buffer) (XLA-CPU wraps the DUS in convert fusions)."""
    x = jax.ShapeDtypeStruct((64, M), jnp.float32)

    def stack(x):
        def body(c, xi):
            return c, (xi * 2).astype(jnp.bfloat16)
        return jax.lax.scan(body, 0.0, x)[1]

    t = analyze(_compile(stack, x).as_text())
    buffer_bytes = 64 * M * 2
    # generous bound: a few row-passes, NOT 64 x buffer
    assert t.bytes < 20 * buffer_bytes, t.bytes


def test_collectives_inside_loop_multiplied():
    import os
    import subprocess
    import sys
    import textwrap
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.hlo_cost import analyze
        mesh = jax.make_mesh((4,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        W = jax.ShapeDtypeStruct((8, 256, 256), jnp.float32)
        x = jax.ShapeDtypeStruct((4, 256), jnp.float32)
        def f(w, x):
            def body(h, wi):
                return jax.lax.with_sharding_constraint(
                    h @ wi, NamedSharding(mesh, P("data"))), None
            return jax.lax.scan(body, x, w)[0].sum()
        c = jax.jit(jax.grad(f), in_shardings=(
            NamedSharding(mesh, P(None, None, "data")),
            NamedSharding(mesh, P("data")))).lower(W, x).compile()
        t = analyze(c.as_text())
        total = sum(t.count_by_collective.values())
        assert total >= 8, t.count_by_collective
        print("OK", t.count_by_collective)
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_breakdown_orders_by_cost():
    a = jax.ShapeDtypeStruct((512, 512), jnp.float32)

    def f(a):
        big = a @ a            # 2*512^3
        small = a[:64, :64] @ a[:64, :64]
        return big.sum() + small.sum()

    bd = breakdown(_compile(f, a).as_text(), top=5)
    assert bd["flops"][0][0] > bd["flops"][-1][0]
    assert bd["flops"][0][0] == pytest.approx(2 * 512 ** 3, rel=0.01)
