"""Validate the loop-aware HLO analyzer against hand-computed costs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import HloModule, analyze


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


class TestFlops:
    def test_plain_matmul(self):
        D = 256
        c = _compile(
            lambda a, b: a @ b,
            jax.ShapeDtypeStruct((D, D), jnp.float32),
            jax.ShapeDtypeStruct((D, D), jnp.float32),
        )
        got = analyze(c)["flops_per_device"]
        np.testing.assert_allclose(got, 2 * D**3, rtol=0.01)

    def test_scanned_matmul_counts_trip_count(self):
        """The whole point: cost_analysis reports 1x, we must report 10x."""
        D, L = 128, 10

        def f(w, x):
            def body(c, _):
                return c @ w, None
            y, _ = jax.lax.scan(body, x, None, length=L)
            return y

        c = _compile(
            f,
            jax.ShapeDtypeStruct((D, D), jnp.float32),
            jax.ShapeDtypeStruct((D, D), jnp.float32),
        )
        r = analyze(c)
        np.testing.assert_allclose(r["flops_per_device"], L * 2 * D**3, rtol=0.05)
        # and the XLA no-loop number really is ~L times smaller
        assert r["xla_flops_noloop"] < r["flops_per_device"] / (L / 2)

    def test_nested_scan(self):
        D, L1, L2 = 64, 3, 5

        def f(w, x):
            def outer(c, _):
                def inner(ci, _):
                    return jnp.tanh(ci @ w), None
                ci, _ = jax.lax.scan(inner, c, None, length=L2)
                return ci, None
            y, _ = jax.lax.scan(outer, x, None, length=L1)
            return y

        c = _compile(
            f,
            jax.ShapeDtypeStruct((D, D), jnp.float32),
            jax.ShapeDtypeStruct((D, D), jnp.float32),
        )
        got = analyze(c)["flops_per_device"]
        want = L1 * L2 * 2 * D**3
        assert want <= got <= want * 1.2, (got, want)


class TestBytesAndCollectives:
    def test_memory_bytes_lower_bound(self):
        """A big copy-like op must move at least in+out bytes."""
        S = 1 << 20
        c = _compile(
            lambda x: x * 2.0 + 1.0,
            jax.ShapeDtypeStruct((S,), jnp.float32),
        )
        b = analyze(c)["bytes_per_device"]
        assert b >= 2 * 4 * S

    def test_collective_bytes_single_allreduce(self):
        if len(jax.devices()) < 1:
            pytest.skip("needs devices")

        # single device: SPMD lowering still emits the collective when we
        # force one through shard_map over a 1-device mesh -> group size 1,
        # which the analyzer must IGNORE (g<=1). So instead just validate
        # the text-level parser on a synthetic HLO snippet.
        text = """
HloModule test, entry_computation_layout={()->f32[]}

ENTRY %main (p: f32[16,1024]) -> f32[16,1024] {
  %p = f32[16,1024]{1,0} parameter(0)
  ROOT %all-reduce.1 = f32[16,1024]{1,0} all-reduce(%p), channel_id=1, replica_groups=[32,16]<=[512], use_global_device_ids=true, to_apply=%add
}
"""
        mod = HloModule(text)
        c = mod.total_cost()
        f = 16 * 1024 * 4
        np.testing.assert_allclose(c.wire_bytes, 2 * f * 15 / 16)
        assert c.coll_ops == {"all-reduce": 1}

    def test_tuple_type_with_index_comments(self):
        """Long tuple types embed /*index=N*/ comments (which contain '=');
        the instruction regex must still match the while op."""
        text = """
HloModule t, entry_computation_layout={()->f32[]}

%b (a: (s32[], f32[8], f32[8], f32[8], f32[8], f32[8], f32[8])) -> (s32[], f32[8], f32[8], f32[8], f32[8], f32[8], f32[8]) {
  %a = (s32[], f32[8]{0}, f32[8]{0}, f32[8]{0}, f32[8]{0}, /*index=5*/f32[8]{0}, f32[8]{0}) parameter(0)
  %g0 = s32[] get-tuple-element(%a), index=0
  %g1 = f32[8]{0} get-tuple-element(%a), index=1
  %e = f32[8]{0} exponential(%g1)
  %c1 = s32[] constant(1)
  %i = s32[] add(%g0, %c1)
  ROOT %t = (s32[], f32[8]{0}, f32[8]{0}, f32[8]{0}, f32[8]{0}, /*index=5*/f32[8]{0}, f32[8]{0}) tuple(%i, %e, %e, %e, %e, %e, %e)
}

%c (a.1: (s32[], f32[8], f32[8], f32[8], f32[8], f32[8], f32[8])) -> pred[] {
  %a.1 = (s32[], f32[8]{0}, f32[8]{0}, f32[8]{0}, f32[8]{0}, /*index=5*/f32[8]{0}, f32[8]{0}) parameter(0)
  %g = s32[] get-tuple-element(%a.1), index=0
  %k = s32[] constant(5)
  ROOT %lt.1 = pred[] compare(%g, %k), direction=LT
}

ENTRY %m (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %z = s32[] constant(0)
  %t0 = (s32[], f32[8]{0}, f32[8]{0}, f32[8]{0}, f32[8]{0}, /*index=5*/f32[8]{0}, f32[8]{0}) tuple(%z, %p, %p, %p, %p, %p, %p)
  %w = (s32[], f32[8]{0}, f32[8]{0}, f32[8]{0}, f32[8]{0}, /*index=5*/f32[8]{0}, f32[8]{0}) while(%t0), condition=%c, body=%b, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %o = f32[8]{0} get-tuple-element(%w), index=1
}
"""
        mod = HloModule(text)
        c = mod.total_cost()
        # exponential: 8 elems x 5 trips (+ tiny add counted too)
        assert 40 <= c.flops <= 50, c.flops

    def test_collective_inside_while_multiplied(self):
        text = """
HloModule test, entry_computation_layout={()->f32[]}

%body (arg: (s32[], f32[128])) -> (s32[], f32[128]) {
  %arg = (s32[], f32[128]{0}) parameter(0)
  %gte = f32[128]{0} get-tuple-element(%arg), index=1
  %ar = f32[128]{0} all-gather(%gte), channel_id=1, replica_groups=[2,256]<=[512], dimensions={0}
  %c1 = s32[] constant(1)
  %gte0 = s32[] get-tuple-element(%arg), index=0
  %add.1 = s32[] add(%gte0, %c1)
  ROOT %t = (s32[], f32[128]{0}) tuple(%add.1, %ar)
}

%cond (arg.1: (s32[], f32[128])) -> pred[] {
  %arg.1 = (s32[], f32[128]{0}) parameter(0)
  %gte.1 = s32[] get-tuple-element(%arg.1), index=0
  %c8 = s32[] constant(8)
  ROOT %lt = pred[] compare(%gte.1, %c8), direction=LT
}

ENTRY %main (p: f32[128]) -> f32[128] {
  %p = f32[128]{0} parameter(0)
  %c0 = s32[] constant(0)
  %t0 = (s32[], f32[128]{0}) tuple(%c0, %p)
  %w = (s32[], f32[128]{0}) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"8"}}
  ROOT %out = f32[128]{0} get-tuple-element(%w), index=1
}
"""
        mod = HloModule(text)
        c = mod.total_cost()
        assert c.coll_ops == {"all-gather": 8}
        f = 128 * 4
        np.testing.assert_allclose(c.wire_bytes, 8 * f * 255 / 256)


class TestRooflineShape:
    def test_terms_present_and_dominant(self):
        D = 512
        c = _compile(
            lambda a, b: a @ b,
            jax.ShapeDtypeStruct((D, D), jnp.bfloat16),
            jax.ShapeDtypeStruct((D, D), jnp.bfloat16),
        )
        r = analyze(c)
        assert set(
            ["t_compute_s", "t_memory_s", "t_collective_s", "dominant"]
        ) <= set(r)
        assert r["t_collective_s"] == 0.0
        assert r["dominant"] in ("compute", "memory")
