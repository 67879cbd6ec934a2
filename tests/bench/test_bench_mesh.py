"""The four-chip cell's path on four virtual CPU devices: a sound run of
the tiny marmoset on a 2x2 mesh is correct, and the same run with the
exchange between mesh rows left out is not.  In a subprocess, because
the device count is fixed when JAX starts."""

import json
import os
import subprocess
import sys

import tiny

CODE = r"""
import json, sys, tempfile
sys.path.insert(0, sys.argv[1])
import tiny
import jax, jax.numpy as jnp
from bench import run as run_mod
from repro.core import distributed as dist

root = tiny.make_root(tempfile.mkdtemp())
out = {}
res = run_mod.run("marmoset-tiny", 2**32 + 9, 0.3, False, jax.devices(),
                  root=root)
out["sound"] = res
real = dist._exchange_finish

def rows_only(payloads, g, *a, **k):
    mirror = real(payloads, g, *a, **k)
    return jnp.where(g["mirror_is_intra"], mirror, 0.0)

dist._exchange_finish = rows_only
out["no_exchange"] = run_mod.run("marmoset-tiny", 2**32 + 9, 0.3, False,
                                 jax.devices(), root=root)
print(json.dumps(out))
"""


def test_mesh_cell_and_exchange_fault():
    env = {k: v for k, v in os.environ.items()}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", CODE,
                        os.path.dirname(tiny.__file__)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    sound, fault = out["sound"], out["no_exchange"]
    assert sound["device"]["count"] == 4
    assert sound["correct"], sound["compared"]
    assert not fault["correct"], fault["compared"]
