"""Benchmark harness: one entry per paper table + TPU-adaptation benches.

Prints ``name,us_per_call,derived`` CSV (one row per measurement) and
persists the same rows to ``BENCH_<UTC-date>.json`` next to the working
directory, so the perf trajectory is recorded run over run (build
throughput, bytes/query, q/s — see benchmarks/jax_bench.py).

Set ``REPRO_BENCH_TINY=1`` to run every bench at smoke sizes (used by the
CI bench-smoke job to keep the JSON plumbing honest).  A bench that
raises is recorded as an ``ERROR`` row and the harness exits non-zero.
"""

from __future__ import annotations

import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    # Anchor on the repo root so the harness runs the same from any CWD
    # (`python benchmarks/run.py`, `python -m benchmarks.run`, CI).
    for p in (_ROOT, os.path.join(_ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks.tables import TABLES
    from benchmarks.jax_bench import JAX_BENCHES

    rows = []
    print("name,us_per_call,derived")
    for name, fn in {**TABLES, **JAX_BENCHES}.items():
        try:
            for seconds, derived in fn():
                print(f"{name},{seconds * 1e6:.1f},{json.dumps(derived, default=float)!r}")
                rows.append(
                    {"name": name, "us_per_call": seconds * 1e6,
                     "derived": derived}
                )
        except Exception as e:  # noqa: BLE001
            print(f"{name},-1,'ERROR: {e!r}'")
            rows.append(
                {"name": name, "us_per_call": -1,
                 "derived": {"error": repr(e)}}
            )

    date = time.strftime("%Y-%m-%d", time.gmtime())
    # always lands at the repo root, wherever the harness was invoked from
    path = os.path.join(_ROOT, f"BENCH_{date}.json")
    with open(path, "w") as f:
        json.dump({"date": date, "rows": rows}, f, indent=1, default=float)
        f.write("\n")
    print(f"# wrote {path} ({len(rows)} rows)", file=sys.stderr)
    errors = [r["name"] for r in rows if r["us_per_call"] < 0]
    if errors:
        print(f"# {len(errors)} bench(es) raised: {errors}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
