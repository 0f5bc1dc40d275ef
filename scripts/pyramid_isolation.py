"""How far a benchmark configuration's pyramid isolates its objects.

    python scripts/pyramid_isolation.py \
        --config bench/configs/map-bit-4m.json --seeds 1,2 --levels 11,16

Makes each seed's data as ``bench/run.py`` makes it for that seed, builds
the configuration's tenant (``build`` from the config) at each depth in
``--levels`` and prints one JSON line per build: the width of every
level, the objects that share their deepest group (the
``unisolated_objects`` gauge of ``SpatialIndex.metrics()``), the number
of such groups and the largest, and the build's seconds.  A depth past
the one where the widths stop growing only repeats the last level.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--levels", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import loadgen
    from repro.index import SpatialIndex

    with open(os.path.join(ROOT, args.config)) as f:
        config = json.load(f)
    tenant = config["tenant"]
    for seed in (int(s) for s in args.seeds.split(",")):
        data = loadgen.make_data(config, loadgen.seeded(seed)[0])
        for levels in (int(v) for v in args.levels.split(",")):
            t0 = time.perf_counter()
            idx = SpatialIndex.build(
                data, structure="pyramid", backend="host",
                build=tenant.get("build"), levels=levels)
            sched = idx.artifacts.schedule
            build_s = time.perf_counter() - t0
            gauge = [m["value"] for m in idx.metrics().to_json()["metrics"]
                     if m["name"] == "repro_index_unisolated_objects"]
            sizes = np.bincount(sched.obj_slot[:sched.n_shared])
            sizes = sizes[sizes > 0]
            print(json.dumps({
                "config": config["name"], "seed": seed, "levels": levels,
                "n": int(data.shape[0]),
                "level_widths": [int(w) for w in sched.n_real],
                "unisolated_objects": gauge[0],
                "shared_groups": int(sizes.size),
                "largest_group": int(sizes.max(initial=0)),
                "build_s": build_s,
            }), flush=True)
            del idx, sched
    return 0


if __name__ == "__main__":
    sys.exit(main())
