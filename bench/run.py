"""Chip benchmark of the design-space sweep and the guided search.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the accelerator JAX finds: set-up
(lowering, compile, warm-up) timed as ``setup_s``, then a window of at
least ``--seconds`` of whole sweep passes or searches, then the comparison
with the plain reference in ``bench/ref``.  The last line of stdout is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and with ``--trace 1`` the device trace's ``breakdown``), and
last ``checks``, each number compared with its limit; the same numbers
close stderr.  With ``--trace 0`` the metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics.

Exits 2, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for.  ``--control f32`` puts the float32 reference in the
program's place, for the runs that show the limits reject it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("f32",), default=None)
    args = ap.parse_args(argv)

    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.lib.harness import NoDevice, run_cell

    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), T_START,
                       control=args.control == "f32")
    except NoDevice as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"bench: check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"bench: correct = {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
