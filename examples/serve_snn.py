"""Multi-tenant SNN serving: N resident brunel sessions, one vmapped step.

The session engine (DESIGN.md §16) holds every tenant's state as one slot
of a fixed batch and advances all residents with ONE jitted
``vmap(engine_step)`` - the consts (graph, param table, config) are built
and compiled once, per-session cost is a slot of state.  This driver:

1. creates N sessions with different seeds (same network - one engine
   serves ONE scenario),
2. steps them interleaved - solo steps, partial waves, full waves -
   exactly as an interactive multi-tenant workload would,
3. streams each session's recent spike window and prints per-tenant rates,
4. (with --ckpt-dir) over-subscribes the slots so sessions park in the
   queue and eviction round-trips through the checkpoint manager.

    PYTHONPATH=src python examples/serve_snn.py --sessions 4 --steps 400
    PYTHONPATH=src python examples/serve_snn.py --sessions 4 --slots 2 \
        --ckpt-dir /tmp/snn_sessions --steps 400
"""

import argparse
import sys

sys.path.insert(0, "src")

import numpy as np

from repro.core import models
from repro.serve.sessions import Backpressure
from repro.serve.snn import SessionEngine
from repro.utils.compile_cache import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sessions", type=int, default=4)
    ap.add_argument("--slots", type=int, default=None,
                    help="resident slots (default: --sessions; fewer "
                         "slots + --ckpt-dir exercises eviction)")
    ap.add_argument("--steps", type=int, default=400,
                    help="steps per session (dt=0.1 ms)")
    ap.add_argument("--scale", type=float, default=0.02)
    ap.add_argument("--sweep", default="flat",
                    help="execution backend: flat | bucketed | pallas")
    ap.add_argument("--ckpt-dir", default=None,
                    help="enables eviction (and slot over-subscription)")
    args = ap.parse_args()
    use_compile_cache()

    slots = args.slots or args.sessions
    eng = SessionEngine(max_sessions=slots, sweep=args.sweep,
                        ckpt_dir=args.ckpt_dir)
    sids = []
    for seed in range(args.sessions):
        sid = eng.create("brunel", seed=seed, scale=args.scale)
        if isinstance(sid, Backpressure):
            print(f"seed {seed}: backpressure ({sid.reason})")
            continue
        sids.append(sid)
        print(f"session {sid}: seed={seed} "
              f"status={eng.session_info(sid)['status']}")

    n = eng.graph.n_local
    print(f"\nnetwork: {n} neurons/session x {slots} slots, "
          f"backend={args.sweep}")

    # interleaved workload: a solo warmup for session 0, then half-waves,
    # then everyone in lockstep for the remainder
    eng.step(sids[0], 40)
    half = sids[:max(len(sids) // 2, 1)]
    eng.step_wave(half, n=40)
    done = {sid: eng.session_info(sid)["step"] for sid in sids}
    remaining = {sid: args.steps - done[sid] for sid in sids}
    # ragged tails: step each session to the same final step count
    for sid in sids:
        r = eng.step(sid, remaining[sid])
        if isinstance(r, Backpressure):   # parked + no eviction path
            print(f"session {sid}: backpressure ({r.reason})")

    print(f"\nper-session rates over the last {min(args.steps, 200)} "
          f"recorded steps:")
    for sid in sids:
        info = eng.session_info(sid)
        if info["step"] == 0:
            continue
        first, bits = eng.spikes(sid, window=200)
        rate = models.firing_rate_hz(np.asarray(bits, np.float32), n)
        print(f"  session {sid}: step={info['step']:>5} "
              f"status={info['status']:>8} rate={rate:6.2f} Hz "
              f"(window [{first}, {first + len(bits)}))")

    s = eng.stats()
    print(f"\nengine: slots={s['slots']} resident={s['resident']} "
          f"evicted={s['evicted']} queued={s['queued']}")


if __name__ == "__main__":
    main()
