"""The measurement tool itself: trip-count-aware HLO cost analysis."""
import jax
import jax.numpy as jnp

from repro.analysis.hlocost import analyze_hlo, parse_computations


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_scan_body_multiplied():
    """flops(scan over N) ~= N * flops(one step) — the exact artifact
    cost_analysis() gets wrong."""
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 64), jnp.float32)

    def one(wv, xv):
        return xv @ wv

    def scanned(wv, xv):
        def body(c, _):
            return c @ wv, None
        y, _ = jax.lax.scan(body, xv, None, length=10)
        return y

    f1 = analyze_hlo(_compile(one, w, x).as_text()).mxu_flops
    f10 = analyze_hlo(_compile(scanned, w, x).as_text()).mxu_flops
    assert abs(f10 - 10 * f1) / (10 * f1) < 0.05, (f1, f10)


def test_matches_xla_on_scan_free():
    def fn(a, b):
        h = jnp.tanh(a @ b)
        return jnp.sum(h @ b.T)
    a = jax.ShapeDtypeStruct((32, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    comp = _compile(fn, a, b)
    mine = analyze_hlo(comp.as_text()).flops
    xla = comp.cost_analysis()["flops"]
    assert abs(mine - xla) / xla < 0.15, (mine, xla)


def test_dot_flops_exact():
    def fn(a, b):
        return a @ b
    a = jax.ShapeDtypeStruct((17, 33), jnp.float32)
    b = jax.ShapeDtypeStruct((33, 9), jnp.float32)
    res = analyze_hlo(_compile(fn, a, b).as_text())
    assert res.mxu_flops == 2 * 17 * 33 * 9


def test_parse_computations_structure():
    def fn(x):
        def body(c, _):
            return jnp.sin(c) * 2, None
        y, _ = jax.lax.scan(body, x, None, length=4)
        return y
    hlo = _compile(fn, jax.ShapeDtypeStruct((16,), jnp.float32)).as_text()
    comps = parse_computations(hlo)
    assert len(comps) >= 2            # entry + loop body at least
    assert any("while" in i.opcode for instrs in comps.values()
               for i in instrs)


def test_nested_scan_multiplies():
    def fn(x):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ c2, None
            y, _ = jax.lax.scan(inner, c, None, length=3)
            return y, None
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y
    hlo = _compile(fn, jax.ShapeDtypeStruct((16, 16), jnp.float32)).as_text()
    res = analyze_hlo(hlo)
    want = 15 * 2 * 16 ** 3           # 5*3 dots
    assert abs(res.mxu_flops - want) / want < 0.05, res.mxu_flops
