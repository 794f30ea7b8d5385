"""Trip-count-aware HLO analyzer: the roofline's foundation."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import make_mesh


def test_scan_flops_multiplied_by_trip_count():
    d, n = 128, 8
    w = jnp.zeros((n, d, d))
    x = jnp.zeros((4, d))
    co = jax.jit(
        lambda x: jax.lax.scan(lambda c, wi: (c @ wi, None), x, w)[0]
    ).lower(x).compile()
    r = analyze(co.as_text())
    assert r["flops"] == pytest.approx(n * 2 * 4 * d * d)
    # sanity: XLA's own analysis counts the body once (the reason this
    # module exists)
    ca = co.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca  # list-of-dicts pre-0.5 jax
    assert ca["flops"] < r["flops"] / (n - 1)


def test_collectives_inside_scan_counted_per_iteration():
    mesh = make_mesh((1,), ("m",))
    P = jax.sharding.PartitionSpec
    w = jnp.zeros((8, 64, 64))
    x = jnp.zeros((4, 64))

    def f(x):
        def step(c, wi):
            return jax.lax.psum(c @ wi, "m"), None
        return jax.lax.scan(step, x, w)[0]

    g = jax.shard_map(f, mesh=mesh, in_specs=P(None, None),
                          out_specs=P(None, None), check_vma=False)
    r = analyze(jax.jit(g).lower(x).compile().as_text())
    assert r["collective_counts"]["all-reduce"] == 8


def test_nested_scan_multiplies():
    x = jnp.zeros((4, 64))
    w = jnp.zeros((3, 5, 64, 64))

    def inner(c, wi):
        return jax.lax.scan(lambda cc, wj: (cc @ wj, None), c, wi)[0]
    co = jax.jit(
        lambda x: jax.lax.scan(lambda c, wi: (inner(c, wi), None), x, w)[0]
    ).lower(x).compile()
    r = analyze(co.as_text())
    assert r["flops"] == pytest.approx(3 * 5 * 2 * 4 * 64 * 64)
