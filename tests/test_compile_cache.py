"""Where the persistent compilation cache goes (repro.utils.compile_cache).

Each case runs in a fresh interpreter: the cache directory is process-wide
JAX config, and the test process must not start caching its own compiles.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

CODE = """
import json, jax
from repro.utils.compile_cache import use_compile_cache
used = use_compile_cache()
print(json.dumps({"used": used,
                  "config": jax.config.jax_compilation_cache_dir,
                  "metadata_in_key":
                      jax.config.jax_compilation_cache_include_metadata_in_key}))
"""


@pytest.mark.parametrize("from_env", [True, False],
                         ids=["env-var", "checkout"])
def test_compile_cache_dir(tmp_path, from_env):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run([sys.executable, "-c", CODE], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    want = (str(tmp_path) if from_env
            else os.path.join(ROOT, ".jax_cache"))
    assert res == {"used": want, "config": want, "metadata_in_key": True}
    if not from_env:
        with open(os.path.join(ROOT, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
