"""The loop-aware HLO analyzer vs closed-form workloads (§Roofline method).

The analyzer must (a) multiply while-loop trip counts - the thing
``cost_analysis()`` gets wrong on CPU - and (b) attribute collective bytes.
Tested on workloads whose exact FLOPs/collective bytes are computable by
hand, on a subprocess 8-device mesh.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")

CODE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.launch.mesh import make_test_mesh
    from repro.utils.hlo_analysis import analyze_hlo

    mesh = make_test_mesh((2, 4), ("data", "model"))
    L, D, B = 5, 64, 8

    def f(params, x):
        def body(h, w):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, params)
        return h.sum()

    ps = NamedSharding(mesh, P(None, None, "model"))
    xs = NamedSharding(mesh, P("data", None))
    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct((L, D, D), jnp.float32, sharding=ps),
        jax.ShapeDtypeStruct((B, D), jnp.float32, sharding=xs)).compile()
    c = analyze_hlo(compiled.as_text())
    # per-device: (B/2, D) @ (D, D/4) = 2*4*16*64 flops x L iterations
    expect_dot = 2 * (B // 2) * (D // 4) * D * L
    # all-gather of the (B/2, D) fp32 block x L iterations
    expect_ag = (B // 2) * D * 4 * L
    print(json.dumps({
        "dot": c.dot_flops, "expect_dot": expect_dot,
        "ag": c.collective_by_kind.get("all-gather", 0),
        "expect_ag": expect_ag,
        "traffic_positive": c.traffic_bytes > 0,
    }))
""")


@pytest.mark.slow
def test_analyzer_exact_on_closed_form():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", CODE], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["dot"] == res["expect_dot"], res
    assert res["ag"] == res["expect_ag"], res
    assert res["traffic_positive"]


def test_parser_units():
    from repro.utils.hlo_analysis import analyze_hlo
    hlo = """
HloModule test

%body (param: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %param = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%param), index=0
  %x = f32[8,8]{1,0} get-tuple-element(%param), index=1
  %ag = f32[8,16]{1,0} all-gather(%x), channel_id=1, dimensions={1}
  %d = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %t = (s32[], f32[8,8]) tuple(%i, %d)
}

%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]) parameter(0)
  ROOT %lt = pred[] constant(true)
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %c = s32[] constant(0)
  %tup = (s32[], f32[8,8]) tuple(%c, %a)
  %w = (s32[], f32[8,8]) while(%tup), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"7"}}
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""
    c = analyze_hlo(hlo)
    assert c.dot_flops == 7 * 2 * 8 * 8 * 8          # trip count applied
    assert c.collective_bytes == 7 * 8 * 16 * 4      # all-gather out bytes
