"""End-to-end DISTRIBUTED training execution (not just lowering):
multi-pod test mesh, sharded params/opt, manual MoE dispatch, optimizer
update, then an ELASTIC restart onto a different mesh shape.

Subprocess with 8 host devices; exercises the full production path:
rules -> shardings -> train step -> checkpoint -> re-mesh -> resume.
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
    import json, tempfile
    import numpy as np
    import jax, jax.numpy as jnp
    from repro import configs
    from repro.checkpoint.manager import CheckpointManager
    from repro.configs.base import TrainConfig
    from repro.data.pipeline import TokenPipeline
    from repro.launch.mesh import make_test_mesh
    from repro.models.model import build_model
    from repro.sharding import rules
    from repro.train.loop import make_train_step
    from repro.train.optimizer import init_opt_state

    res = {}
    cfg = configs.get_smoke("qwen3-moe-30b-a3b")   # exercises manual EP
    m = build_model(cfg)
    tcfg = TrainConfig(optimizer="adamw", lr=2e-3)
    pipe = TokenPipeline(vocab_size=cfg.vocab_size, seq_len=16,
                         global_batch=4, seed=5)

    # ---- phase 1: multi-pod mesh (2,2,2) -------------------------------
    mesh1 = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
    with rules.use_mesh(mesh1):
        params = m.init(jax.random.key(0))
        p_sh = rules.param_specs(mesh1, jax.eval_shape(lambda: params))
        params = jax.tree.map(jax.device_put, params, p_sh)
        opt = init_opt_state(tcfg, params)
        o_sh = rules.param_specs(mesh1, jax.eval_shape(lambda: opt))
        opt = jax.tree.map(jax.device_put, opt, o_sh)
        step = jax.jit(make_train_step(m, tcfg, microbatches=2),
                       donate_argnums=(0, 1))
        losses = []
        for i in range(6):
            batch = {"tokens": jnp.asarray(pipe.batch(i)["tokens"])}
            params, opt, met = step(params, opt, batch, jnp.asarray(i))
            losses.append(float(met["loss"]))
    res["losses1"] = losses
    res["sharded"] = bool(any(
        not l.sharding.is_fully_replicated for l in jax.tree.leaves(params)))

    tmp = tempfile.mkdtemp()
    mgr = CheckpointManager(tmp)
    mgr.save(6, (params, opt), metadata={"step": 6})

    # ---- phase 2: elastic restart on a SMALLER mesh (2,2) --------------
    mesh2 = make_test_mesh((2, 2), ("data", "model"))
    with rules.use_mesh(mesh2):
        p2_sh = rules.param_specs(mesh2, jax.eval_shape(lambda: params))
        o2_sh = rules.param_specs(mesh2, jax.eval_shape(lambda: opt))
        (params2, opt2), meta = mgr.restore((params, opt),
                                            shardings=(p2_sh, o2_sh))
        step2 = jax.jit(make_train_step(m, tcfg, microbatches=2),
                        donate_argnums=(0, 1))
        for i in range(meta["step"], meta["step"] + 3):
            batch = {"tokens": jnp.asarray(pipe.batch(i)["tokens"])}
            params2, opt2, met = step2(params2, opt2, batch,
                                       jnp.asarray(i))
            losses.append(float(met["loss"]))
    res["losses2"] = losses[6:]
    res["resume_step"] = meta["step"]
    print(json.dumps(res))
""")


@pytest.mark.slow
def test_distributed_train_and_elastic_restart():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", CODE], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    l1 = res["losses1"]
    assert all(np.isfinite(v) for v in l1), l1
    assert l1[-1] < l1[0], l1          # training moves on the 3-axis mesh
    assert res["sharded"]              # params actually sharded
    assert res["resume_step"] == 6
    l2 = res["losses2"]
    assert all(np.isfinite(v) for v in l2), l2
    assert l2[-1] < l1[0]              # keeps improving after re-mesh


import numpy as np  # noqa: E402  (used in asserts above)
