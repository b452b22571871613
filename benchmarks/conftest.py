"""A driver's kind, for ``tests/test_rehearse.py``.

That file takes a cell's planted faults by the name of its traffic's
driver (``FAULTS[KIND[w]]``) and knows two names, ``train`` and
``serve``.  Since PR 33 a cell's driver is ``train_ffm``: a driver of
the kind ``train`` (it drives ``cli.main(["train", cfg])`` under
``drivers/train.py``'s own observer and takes its control and its two
faults), which the lookup answers with a KeyError at import -- and then
the rehearsals, controls and faults of every cell stop with it.

No file that is under ``benchmarks/`` may be edited by the PR that adds a
cell, so the rule stands here, in a new file: **a driver named
``<kind>_<model>`` is of the kind ``<kind>``**, and while
``test_rehearse.py`` is imported (and only then) ``harness.load_cell``
says the kind where the traffic file says the driver.  The tests
themselves start ``run.py`` as a process, which reads the traffic file
as it is.  A ``benchmark`` PR that takes ``FAULTS`` from the drivers
deletes this file (PERF.md section 7).
"""

import contextlib
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


@contextlib.contextmanager
def drivers_by_kind():
    from fmbench import harness

    load_cell = harness.load_cell

    def by_kind(workload):
        cell = load_cell(workload)
        traffic = dict(cell["traffic"])
        traffic["driver"] = traffic["driver"].split("_")[0]
        return {**cell, "traffic": traffic}

    harness.load_cell = by_kind
    try:
        yield
    finally:
        harness.load_cell = load_cell


@pytest.hookimpl(hookwrapper=True)
def pytest_make_collect_report(collector):
    path = getattr(collector, "path", None)
    if path is not None and path.name == "test_rehearse.py":
        with drivers_by_kind():
            yield
    else:
        yield
