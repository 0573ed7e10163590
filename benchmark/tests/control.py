"""The control of "how `correct` is decided": the cell run with ONE guarantee
of its configuration broken — the step that would tempt a later PR, because
each makes the run faster — which has to come out `correct: false`.

    python benchmark/tests/control.py --control rare_checkpoint \
        --workload q7.sat --seed 7 --seconds 20 --trace 0

Controls (the engine's own switches, nothing patched):
- `rare_checkpoint`: `checkpoint_frequency = 2` — only every second barrier
  is a checkpoint ("every checkpoint durable" broken: half of the window's
  barriers never reach a manifest swap, and the committed offset lags the
  injected barriers).

Same chip rules as `benchmark/run.py`, same output; exits 0 when the run
ended, whatever `correct` says. Not part of the benchmark's command.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def rare_checkpoint(cell) -> None:
    cell.config = {**cell.config, "checkpoint_frequency": 2}


CONTROLS = {"rare_checkpoint": rare_checkpoint}


def main() -> int:
    from benchmark import run
    ap = run.parser()
    ap.add_argument("--control", required=True, choices=sorted(CONTROLS))
    args = ap.parse_args()
    return run.main(args, mutate=CONTROLS[args.control])


if __name__ == "__main__":
    sys.exit(main())
