#!/usr/bin/env python3
"""The control of the comparison in ``chipbench/compare.py``.

    python3 -m chipbench.control --scale 1 --seeds 1 2 3 [--per-shape 6]

The configurations state DOUBLE (float64) aggregates, so the step below
them is float32.  The control puts the plain reference, computed on the
same tables with every float64 column held in float32, in the engine's
place, and reads the number the comparison reads: the widest relative gap
of a floating cell, for each shape and parameter set.  It has to come out
as not correct.  The benchmark's own runs do not run it; PERF.md keeps
what it read at SF1 beside the limit, and
``tests/chipbench/test_chipbench_compare.py`` runs it at SF0.01.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from chipbench import compare, run, traffic  # noqa: E402
from chipbench.data import tpch_gen  # noqa: E402


def float32_frames(frames: dict) -> dict:
    """The same tables with every float64 column in float32."""
    return {name: frame.astype({c: np.float32 for c in frame.columns
                                if frame[c].dtype == np.float64})
            for name, frame in frames.items()}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--scale", type=float, default=1.0)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--per-shape", type=int, default=6)
    parser.add_argument("--mix", default="power")
    args = parser.parse_args(argv)
    shapes = {name: run.load_by_path("shapes", name)
              for name in traffic.load_mix(args.mix)["shapes"]}
    for seed in args.seeds:
        frames = tpch_gen.generate(args.scale, seed)
        low = float32_frames(frames)
        draws = traffic.Draws(shapes, seed)
        for name, shape in shapes.items():
            gaps = []
            for _ in range(args.per_shape):
                params = draws.fresh(name)["params"]
                gap, mismatched = compare.compare_frames(
                    shape.reference(low, **params),
                    shape.reference(frames, **params))
                gaps.append(gap)
            print(json.dumps({
                "control": "float32", "seed": seed, "shape": name,
                "min_gap": min(gaps), "max_gap": max(gaps),
                "limit": compare.LIMITS["max_rel_gap"],
                "not_correct": min(gaps) > compare.LIMITS["max_rel_gap"]}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
