"""The HLO collective-bytes parser: trip-count correction on real compiled
modules (the §Roofline methodology's measured leg)."""


import numpy as np
import jax
import jax.numpy as jnp

from repro.launch.hlo_analysis import collective_bytes, roofline_terms


def test_trip_count_scales_loop_collectives():
    """A psum inside a lax.scan must be counted trip-count times."""
    if jax.device_count() < 1:
        return
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1), ("d",))

    def f(x):
        def body(c, _):
            s = jax.lax.psum(c, "d")
            return c + 0.001 * s, None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    from jax.sharding import PartitionSpec as P
    fn = jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=True)
    compiled = jax.jit(fn).lower(
        jax.ShapeDtypeStruct((64, 32), jnp.float32)).compile()
    txt = compiled.as_text()
    coll = collective_bytes(txt)
    n_ar_static = coll["counts"]["all-reduce"]
    n_ar_dynamic = coll["dynamic_counts"]["all-reduce"]
    if n_ar_static:  # single-device psum may fold away entirely
        assert n_ar_dynamic >= 7 * 1.0 or n_ar_dynamic == n_ar_static


def test_parser_on_synthetic_hlo():
    hlo = """
HloModule test

%body.1 (p: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {
  %p = (s32[], f32[8,4]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,4]{1,0} get-tuple-element(%p), index=1
  %ar = f32[8,4]{1,0} all-reduce(%x), channel_id=1, to_apply=%add.0
  ROOT %t = (s32[], f32[8,4]) tuple(%i, %ar)
}

%cond.1 (p2: (s32[], f32[8,4])) -> pred[] {
  %p2 = (s32[], f32[8,4]) parameter(0)
  %i2 = s32[] get-tuple-element(%p2), index=0
  %c = s32[] constant(5)
  ROOT %lt = pred[] compare(%i2, %c), direction=LT
}

ENTRY %main (a: f32[8,4]) -> f32[8,4] {
  %a = f32[8,4]{1,0} parameter(0)
  %init = (s32[], f32[8,4]) tuple(s32[] constant(0), %a)
  %w = (s32[], f32[8,4]) while(%init), condition=%cond.1, body=%body.1
  %ag = f32[16,4]{1,0} all-gather(%a), dimensions={0}
  ROOT %out = f32[8,4]{1,0} get-tuple-element(%w), index=1
}
"""
    coll = collective_bytes(hlo)
    # all-reduce: 8*4*4 bytes, in a 5-trip while -> x5
    assert coll["by_kind"]["all-reduce"] == 8 * 4 * 4 * 5
    assert coll["dynamic_counts"]["all-reduce"] == 5
    # all-gather at top level: operand is f32[8,4] -> 128 bytes, x1
    assert coll["by_kind"]["all-gather"] == 8 * 4 * 4
    assert coll["total_bytes"] == 8 * 4 * 4 * 6


def test_roofline_terms_dominance():
    r = roofline_terms(197e12, 100e9, 1e9)       # 1 s compute, .12 s mem
    assert r["dominant"] == "compute"
    assert abs(r["compute_s"] - 1.0) < 1e-9
    r = roofline_terms(1e12, 819e9, 500e9)       # 10 s collective
    assert r["dominant"] == "collective"
    assert abs(r["collective_s"] - 10.0) < 1e-9
