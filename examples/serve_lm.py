"""Batched serving example: wave admission + greedy decode over KV caches.

    PYTHONPATH=src python examples/serve_lm.py [--arch qwen2.5-3b]
"""

import argparse
import sys
sys.path.insert(0, "src")

import jax

from repro import configs
from repro.models.model import build_model
from repro.serve.engine import BatchServer
from repro.utils.compile_cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--max-new", type=int, default=24)
    args = ap.parse_args()
    use_compile_cache()

    cfg = configs.get_smoke(args.arch)
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    server = BatchServer(m, params, slots=4, max_len=128, eos_id=-1)

    requests = [
        [11, 23, 5, 42],
        [7, 7, 7],
        [101, 55, 2, 9, 13, 28],
        [64],
    ]
    outs, stats = server.serve(requests, max_new_tokens=args.max_new)
    for i, o in enumerate(outs):
        print(f"req{i}: prompt={requests[i]} -> {o[:12]}...")
    print(f"prefill {stats.prefill_s*1e3:.1f} ms, "
          f"decode {stats.decode_tok_per_s:.1f} tok/s "
          f"({stats.tokens_out} tokens)")
    print("ok")


if __name__ == "__main__":
    main()
