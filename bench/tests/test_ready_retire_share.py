"""``ready_retire_share.p50`` on hand-made dispatch records: the share of
the window's dispatches retired on readiness, and nothing where the
records do not name what retired a batch."""
import dataclasses
import json
import os

import numpy as np
import pytest

from bench import harness, spec
from repro.core.scheduler import DispatchRecord

NAME = "ready_retire_share.p50"


@pytest.fixture(scope="module")
def read():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        entry = {m["name"]: m for m in json.load(f)["per_layer"]}[NAME]
    return spec.load_reader(entry)


def _run(dispatches, t_start=10.0, t_end=30.0):
    empty = np.zeros(0)
    return harness.Run(t_end - t_start, t_start, t_end, empty, empty, empty,
                       dispatches, [], 0.0, [], None, None)


def _rec(started, retired_by, failed=False):
    return DispatchRecord("m", 4, 4, started, 0.01, "full", failed=failed,
                          retired_by=retired_by)


def test_share_of_the_window_retired_on_readiness(read):
    recs = [_rec(9.99, "ready"),                 # before the window
            _rec(10.0, "ready"),                 # its edges are inside
            _rec(15.0, "slot"),
            _rec(20.0, "ready"),
            _rec(21.0, "ready", failed=True),    # re-dispatched: not counted
            _rec(25.0, "sync"),
            _rec(30.0, "ready"),
            _rec(30.01, "slot")]                 # the drain, after it
    assert read(_run(recs)) == pytest.approx(100.0 * 3 / 5)


def test_nothing_to_read_without_the_field_or_the_dispatches(read):
    @dataclasses.dataclass(frozen=True)
    class OlderRecord:                           # a program before the field
        started: float
        failed: bool = False

    assert read(_run([OlderRecord(12.0), OlderRecord(13.0)])) is None
    assert read(_run([])) is None
    assert read(_run([_rec(5.0, "ready")])) is None
