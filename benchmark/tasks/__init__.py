"""What a row is, one module per task, found by the ``task`` key of a
configuration (``images`` where the key is absent).

A task module owns everything of the harness that knows whether a job
trains on labelled images or on a token stream:

``job_sizes(argv)``                 the sizes read back from the job's argv
``make_rows(seed, sizes, n_test, model)``   the rows, in bulk from the seed
``bundle(rows, config, cfg)``       what the trainer is handed as its data
``plan_batches(shares, sizes)``     per-worker widths of a recorded plan
``epoch_samples(shares, sizes)``    samples an epoch under that plan trains
``plan_errors(epochs, sizes)``      the two exact checks on the window
``job_keys(config, sizes)``         the task's part of the reference's job
``train_epoch(params, rows, model, job, ...)``   the job's recipe: the plain
                                    reference's own copy of which rows a step
                                    trains on, their weights, the loss and
                                    the update, with ``FAULTS`` to plant

``run.py``, ``harness.py``, ``sut.py`` and ``control.py`` go through these
names and hold nothing of either task; a third task is a third file.
"""

from __future__ import annotations

import importlib


def load(config: dict):
    """The module of the configuration's task."""
    return importlib.import_module(f"{__package__}.{config.get('task', 'images')}")
