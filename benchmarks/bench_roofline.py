"""Roofline table (deliverable g): reads the dry-run JSON cache and emits per
(arch x shape x mesh): the three roofline terms, the dominant bottleneck, and
MODEL_FLOPS/HLO_FLOPs.  Recorded to ``BENCH_roofline.json`` (override with
env BENCH_ROOFLINE_OUT) like the other benches."""

from __future__ import annotations

import glob
import json
import os
import sys

try:
    from benchmarks.common import Recorder
except ModuleNotFoundError:  # direct `python benchmarks/bench_roofline.py`
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, _root)
    sys.path.insert(0, os.path.join(_root, "src"))
    from benchmarks.common import Recorder

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")


def load_cells() -> list[dict]:
    cells = []
    for path in sorted(glob.glob(os.path.join(RESULTS, "*.json"))):
        with open(path) as f:
            cells.append(json.load(f))
    return cells


def run():
    rec = Recorder()
    cells = load_cells()
    if not cells:
        rec.emit("roofline", 0.0,
                 "NO_DRYRUN_CACHE(run python -m repro.launch.dryrun)")
    for c in cells:
        r = c["roofline"]
        frac = c.get("useful_flops_frac")
        frac_s = f"{frac:.3f}" if frac is not None else "n/a"
        rec.emit(
            f"roofline_{c['key']}",
            0.0,
            f"compute_s={r['compute_s']:.3e};memory_s={r['memory_s']:.3e};"
            f"collective_s={r['collective_s']:.3e};bottleneck={c['bottleneck']};"
            f"useful_flops_frac={frac_s}",
        )
    if cells:
        n_bad = sum(1 for c in cells if c["bottleneck"] != "compute_s")
        rec.emit("roofline_summary", 0.0,
                 f"cells={len(cells)};non_compute_bound={n_bad}")
    rec.write_json(os.environ.get("BENCH_ROOFLINE_OUT", "BENCH_roofline.json"))


if __name__ == "__main__":
    from repro.launch.env import enable_compilation_cache

    enable_compilation_cache()
    run()
