"""Sharding rules + a reduced-mesh dry-run (8 host devices, subprocess).

The full 512-device dry-run is exercised by ``launch/dryrun.py`` (results in
EXPERIMENTS.md); here the same build path must lower+compile on a small mesh
for representative archs x shapes, proving the cell builder is
mesh-parametric.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def test_param_rules_cover_all_archs():
    """Every parameter leaf of every smoke arch gets a valid spec on an
    abstract 4x4 mesh, and at least half the big leaves are sharded."""
    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.models.model import build_model
    from repro.sharding import rules
    from jax.sharding import AbstractMesh

    mesh = AbstractMesh((4, 4), ("data", "model"))
    for arch in configs.ARCH_NAMES:
        cfg = configs.get_smoke(arch)
        m = build_model(cfg)
        sds = jax.eval_shape(lambda: m.init(jax.random.key(0)))
        specs = rules.param_specs(mesh, sds)
        n_sharded = 0
        n_big = 0
        for s, sp in zip(jax.tree.leaves(sds), jax.tree.leaves(
                specs, is_leaf=lambda x: hasattr(x, "spec"))):
            for dim, ax in enumerate(sp.spec):
                if ax is not None:
                    size = 4 if not isinstance(ax, tuple) else 16
                    assert s.shape[dim] % size == 0, (arch, s.shape, sp)
            if np.prod(s.shape) >= 64 * 64:
                n_big += 1
                if any(a is not None for a in sp.spec):
                    n_sharded += 1
        if n_big:
            assert n_sharded >= n_big // 2, arch


def test_cache_specs_head_vs_seq_fallback():
    import jax
    import jax.numpy as jnp
    from repro.sharding import rules
    from jax.sharding import AbstractMesh

    mesh = AbstractMesh((2, 8), ("data", "model"))
    cache = {"period": {"k": jax.ShapeDtypeStruct((4, 16, 64, 2, 8),
                                                  jnp.bfloat16),
                        "v": jax.ShapeDtypeStruct((4, 16, 64, 2, 8),
                                                  jnp.bfloat16)}}
    specs = rules.cache_specs(mesh, cache)
    spec = jax.tree.leaves(specs, is_leaf=lambda x: hasattr(x, "spec"))[0]
    # kv heads = 2 cannot shard over model=8 -> sequence dim takes "model"
    assert spec.spec[2] == ("model",) or spec.spec[2] == "model", spec


DRYRUN_SMALL = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    import dataclasses
    from repro import configs
    from repro.configs.base import ShapeConfig
    from repro.launch import dryrun
    from repro.launch.mesh import make_test_mesh
    from repro.sharding import rules

    mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
    results = {}
    cells = [
        ("qwen2.5-3b", ShapeConfig("train", "train", 64, 8, 2)),
        ("jamba-v0.1-52b", ShapeConfig("prefill", "prefill", 64, 4)),
        ("deepseek-v3-671b", ShapeConfig("decode", "decode", 64, 8)),
        ("rwkv6-3b", ShapeConfig("decode", "decode", 64, 1)),
        ("whisper-tiny", ShapeConfig("train", "train", 24, 4)),
        ("internvl2-1b", ShapeConfig("prefill", "prefill", 32, 4)),
    ]
    for arch, shape in cells:
        cfg = configs.get_smoke(arch)
        fn, args, donate, out_sh = dryrun.build_cell(cfg, shape, mesh)
        with rules.use_mesh(mesh):
            compiled = jax.jit(fn, donate_argnums=donate,
                               out_shardings=out_sh).lower(*args).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        results[f"{arch}/{shape.kind}"] = float(ca.get("flops", -1)) > 0
    print(json.dumps(results))
""")


@pytest.mark.slow
def test_dryrun_small_mesh_multipod():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", DRYRUN_SMALL], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert len(res) == 6 and all(res.values()), res


def test_input_specs_match_model_inputs():
    """input_specs must produce exactly the batch keys each family's loss
    expects (catches spec drift)."""
    import jax
    from repro import configs
    from repro.configs.shapes import SHAPES
    from repro.launch.dryrun import input_specs
    from jax.sharding import AbstractMesh

    mesh = AbstractMesh((16, 16), ("data", "model"))
    for arch in configs.ARCH_NAMES:
        cfg = configs.get(arch)
        sp = input_specs(cfg, SHAPES["train_4k"], mesh)
        assert "tokens" in sp
        if cfg.family == "audio":
            assert "frames" in sp
        if cfg.family == "vlm":
            assert "patches" in sp
        spd = input_specs(cfg, SHAPES["decode_32k"], mesh)
        assert set(spd) == {"token", "pos"}
