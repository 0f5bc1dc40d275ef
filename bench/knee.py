"""Find an open-loop cell's knee: sweep offered rates on one built front
end and report, per rate, whether completions kept up with arrivals.

    python3 bench/knee.py --workload paper-mqr-30k.nearest --seed 5 \
        --seconds 10 --rates 400,800,1600

A rate is kept up with when the requests completed by the window's end
cover its arrivals up to the last deadline's worth, and the queue left at
the end drains within one deadline.  The knee is the highest such rate;
a cell's traffic file fixes its rate at about four fifths of it.
"""

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import contextlib

    import jax

    from bench import harness

    cell = harness.load_cell(ROOT, args.workload)
    if cell.traffic["loop"] != "open":
        raise SystemExit("knee.py sweeps open-loop cells only")
    harness.enable_compile_cache(ROOT)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"knee: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 3
    inp = harness.make_inputs(cell, args.seed)
    front = harness.build_front(inp)
    harness.warm(front, inp, inp.window_requests(args.seconds)[0])
    for rate in (float(r) for r in args.rates.split(",")):
        reqs, offsets = inp.window_requests(args.seconds, rate)
        tickets, t0, end, late = harness.drive(
            front, inp, reqs, offsets, args.seconds,
            lambda name: contextlib.nullcontext())
        lat = np.array([t.t_complete - t.t_arrival for t in tickets])
        done_in = sum(t.t_complete <= end for t in tickets)
        row = {
            "rate": rate, "sent": len(tickets), "done_in_window": done_in,
            "drain_s": max(t.t_complete for t in tickets) - end,
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p95_ms": float(np.percentile(lat, 95) * 1e3),
            "late_max_ms": float(np.max(late) * 1e3),
            "launches": len({t.t_launch for t in tickets}),
        }
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
