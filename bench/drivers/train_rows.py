"""Training cells as `bench.drivers.train` runs them, with the reference of
`bench.reference.lm_rows` over the cell's chips (one row's gradient a
chip), for a configuration whose whole-batch reference gradient does not
fit one chip beside its parameters."""
from __future__ import annotations

import contextlib
import functools
import types

from bench.drivers import train
from bench.harness import Outcome, Run
from bench.reference import lm, lm_rows


@contextlib.contextmanager
def _rows_reference(devices):
    ref = types.SimpleNamespace(
        layout=lm.layout, stacked=lm.stacked, change_norms=lm.change_norms,
        run_reference=functools.partial(lm_rows.run_reference, devices=devices))
    old, train.ref_lm = train.ref_lm, ref
    try:
        yield
    finally:
        train.ref_lm = old


def run(run: Run) -> Outcome:
    with _rows_reference(run.devices):
        return train.run(run)
