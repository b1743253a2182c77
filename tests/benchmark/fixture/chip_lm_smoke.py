#!/usr/bin/env python3
"""The fixture language-model cells through ``benchmark/run.py``, for a look
on the chip (ISSUE 26 (b)): does ``LMTrainer`` run through the harness there,
which path does each epoch take, what does set-up cost. Smoke timings, never
a rate.

    python3 tests/benchmark/fixture/chip_lm_smoke.py <cell> [run.py's other arguments]

It sets ``LMTrainer.DROPOUT`` to 0.0 first (a class constant of 0.2 in the
program; a plain reference cannot follow its masks) and hands the rest to
``run.main`` with the fixture's manifest. Without ``--rehearsal`` it needs a
TPU, as every run of ``run.py`` does.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    from benchmark import run
    from dynamic_load_balance_distributeddnn_tpu.train.lm_engine import LMTrainer

    LMTrainer.DROPOUT = 0.0
    return run.main(["--manifest", os.path.join(HERE, "manifest.json"), "--workload", *argv])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
