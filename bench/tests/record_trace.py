"""Record the small trace that ``test_bench_trace.py`` reduces: one traced
run of the render cell, cut to n = 20,000 objects, 16 clients and a
half-second window, on one TPU chip.

    python3 bench/tests/record_trace.py OUT_DIR

Writes ``OUT_DIR/render_small.xplane.pb`` and ``OUT_DIR/render_small.json``
(the run's result line), which the test keeps in
``bench/tests/data/``.
"""

import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(out: str) -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness, tracereduce

    tdir = tempfile.mkdtemp(prefix="bench-record-")
    runs = []
    real_summarize = tracereduce.summarize

    def keep(path, **kw):
        runs.append(path)
        return real_summarize(path, **kw)

    tracereduce.summarize = keep
    result = harness.run_cell(
        ROOT, "map-pyramid-4m.render", 12, 0.5, True, trace_dir=tdir,
        overrides={"config": {"n": 20000}, "traffic": {"clients": 16}})
    os.makedirs(out, exist_ok=True)
    shutil.copy(runs[0], os.path.join(out, "render_small.xplane.pb"))
    with open(os.path.join(out, "render_small.json"), "w") as f:
        json.dump(result, f, indent=1)
    shutil.rmtree(tdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
