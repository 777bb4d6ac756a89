"""Unit tests for the loop-aware HLO analyzer (the §Roofline methodology).

Validates the central claim of EXPERIMENTS.md §Methodology: XLA's
cost_analysis counts while bodies once; our parser recovers the true
totals using known_trip_count.
"""
import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.launch import hlo_analysis as ha

L, N, D = 8, 64, 128


def _scanned(x, Ws):
    y, _ = jax.lax.scan(lambda c, w: (c @ w, None), x, Ws)
    return y


def test_cost_analysis_counts_loop_bodies_once():
    c = ha.xla_cost_analysis(jax.jit(_scanned).lower(
        jnp.ones((N, D)), jnp.ones((L, D, D))).compile())
    one_layer = 2 * N * D * D
    assert abs(c["flops"] - one_layer) < one_layer * 0.01


def test_analyzer_recovers_full_flops():
    Ws = jnp.ones((L, D, D))
    x = jnp.ones((N, D))
    hlo = jax.jit(_scanned).lower(x, Ws).compile().as_text()
    stats = ha.analyze(hlo, [L])
    want = 2 * N * D * D * L
    assert abs(stats.flops - want) < want * 0.01


def test_analyzer_nested_scans():
    """Outer scan (3) x inner scan (L) multiply correctly."""
    Ws = jnp.ones((L, D, D))
    x = jnp.ones((N, D))

    def outer(x, Ws):
        def body(c, _):
            return _scanned(c, Ws), None
        y, _ = jax.lax.scan(body, x, None, length=3)
        return y

    hlo = jax.jit(outer).lower(x, Ws).compile().as_text()
    stats = ha.analyze(hlo, [3, L])
    want = 2 * N * D * D * L * 3
    assert abs(stats.flops - want) < want * 0.01


def test_known_trip_count_overrides_depth_guess():
    """Even with WRONG depth hints, backend_config trips win."""
    Ws = jnp.ones((L, D, D))
    x = jnp.ones((N, D))
    hlo = jax.jit(_scanned).lower(x, Ws).compile().as_text()
    stats = ha.analyze(hlo, [999])           # bogus hint
    want = 2 * N * D * D * L
    assert abs(stats.flops - want) < want * 0.01


def test_collective_counting_with_loops():
    """psum inside a scan counts once per trip."""
    mesh = jax.make_mesh((1,), ("x",), axis_types=(AxisType.Auto,))

    def f(v):
        def body(c, _):
            return c + jax.lax.psum(c, "x"), None
        y, _ = jax.lax.scan(body, v, None, length=5)
        return y

    from jax.sharding import PartitionSpec as P
    try:
        from jax import shard_map
        fn = shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=False)
    except ImportError:
        from jax.experimental.shard_map import shard_map as sm
        fn = sm(f, mesh=mesh, in_specs=P(), out_specs=P(), check_rep=False)
    with mesh:
        hlo = jax.jit(fn).lower(jnp.ones((16, 128))).compile().as_text()
    stats = ha.analyze(hlo, [5])
    # 1-device psum may be optimized away entirely; the invariant is that
    # IF present it is multiplied by the trip count (payload % trip == 0)
    if stats.collective_total:
        assert stats.collective_total % 5 == 0


def test_multistep_structure_helpers():
    """The DESIGN.md §9 structural analyzers: a K-step scan over a layer
    scan shows up as a depth-0 while of trip K wrapping a depth-1 while
    of trip L, with no host transfers, and the entry output is the
    carried tensor (not per-step intermediates)."""
    K = 4
    Ws = jnp.ones((L, D, D))
    x = jnp.ones((N, D))

    def ksteps(x, Ws):
        def body(c, _):
            return _scanned(c, Ws), None
        y, _ = jax.lax.scan(body, x, None, length=K)
        return y

    hlo = jax.jit(ksteps).lower(x, Ws).compile().as_text()
    trips = ha.while_trip_structure(hlo)
    assert (0, K) in trips, trips
    assert (1, L) in trips, trips
    assert ha.host_transfer_count(hlo) == 0
    outs = ha.entry_output_shapes(hlo)
    assert ("f32", [N, D]) in outs, outs
