"""Record the small scoped trace that ``test_bench_phases.py`` reads.

    python tests/bench/record_phases_trace.py <out_dir>

On a TPU: one traced call (10 steps) of the tiny hpc_benchmark twin with
STDP on (``hpc-stdp-tiny``, 225 neurons, 253,125 synapses), run by
``bench.run`` in a scratch benchmark root.  Writes the trace (device
events only) as ``<out_dir>/phases.xplane.pb.gz`` and the compiled
program's HLO text as ``<out_dir>/phases_hlo.txt.gz``, and prints the
run's result.
"""

import gzip
import json
import shutil
import sys
import tempfile
from pathlib import Path

import jax

import tiny
from bench import run as run_mod
from bench import trace as trace_mod


def _device_only(start_trace):
    """``jax.profiler.start_trace`` without the host's and Python's
    events, which would make the file megabytes."""
    def start(log_dir, **kw):
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 0
        opts.python_tracer_level = 0
        return start_trace(log_dir, profiler_options=opts)
    return start


def main(out: Path) -> int:
    devices = run_mod.require_chips(1)
    jax.profiler.start_trace = _device_only(jax.profiler.start_trace)
    root = tiny.make_root(tempfile.mkdtemp())
    res = run_mod.run("hpc-stdp-tiny", 2**32 + 15, 0.0, True, devices,
                      root=root)
    src = root / ".bench_trace" / "hpc-stdp-tiny"
    out.mkdir(parents=True, exist_ok=True)
    with open(trace_mod.find_xplane(str(src)), "rb") as fin, \
            gzip.open(out / "phases.xplane.pb.gz", "wb") as fout:
        shutil.copyfileobj(fin, fout)
    with gzip.open(out / "phases_hlo.txt.gz", "wt") as fh:
        fh.write((src / "hlo.txt").read_text())
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main(Path(sys.argv[1])))
