"""Parameter inversion demo: recover brunel's (g, eta) by gradient
descent through the simulator (DESIGN.md §17).

Builds the quick-geometry brunel network at the TRUE parameters, records
per-neuron PSTH targets at two drive conditions, then fits ``(g, eta)``
from a perturbed init: an Adam descent in log-parameter space through
the surrogate-gradient rollout, followed by an eta-profiled g scan that
pins the sharp joint minimum.  The full fit takes ~4-6 CPU minutes and
lands within 5% relative error; ``--smoke`` runs the CI-sized fit
(~1 min, looser landing).

    PYTHONPATH=src python examples/fit_brunel.py --init-g 4.0 --init-eta 2.5
    PYTHONPATH=src python examples/fit_brunel.py --smoke
"""

import argparse
import sys
import time

sys.path.insert(0, "src")

from repro.diff import inverse
from repro.utils.compile_cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser(
        description="fit brunel (g, eta) from PSTH targets by gradient")
    ap.add_argument("--init-g", type=float, default=4.0,
                    help="perturbed init for g (truth: 5.0)")
    ap.add_argument("--init-eta", type=float, default=2.5,
                    help="perturbed init for eta (truth: 2.0)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized fit: shorter rollouts, one profiled "
                         "round (~1 min)")
    args = ap.parse_args()
    use_compile_cache()

    kwargs = {}
    if args.smoke:
        kwargs = dict(n_steps=300, adam_iters=8, g_rounds=((0.12, 5),),
                      eta_radii=(0.003, 0.001), eta_points=4)

    print(f"fitting from init (g={args.init_g}, eta={args.init_eta}) ...")
    t0 = time.perf_counter()
    res = inverse.invert_brunel(args.init_g, args.init_eta, **kwargs)
    dt = time.perf_counter() - t0

    err = res.rel_error
    print(f"  true     g={res.true_g:.4f}  eta={res.true_eta:.4f}")
    print(f"  fitted   g={res.g:.4f}  eta={res.eta:.4f}")
    print(f"  rel err  g={100 * err['g']:.2f}%  "
          f"eta={100 * err['eta']:.2f}%")
    print(f"  loss {res.loss_history[0]:.3e} -> {res.final_loss:.3e} "
          f"({res.n_evals} loss evals, {dt:.0f}s)")
    bar = 0.25 if args.smoke else 0.05
    ok = err["g"] <= bar and err["eta"] <= bar
    print("  OK" if ok else f"  MISSED the {bar:.0%} bar")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
