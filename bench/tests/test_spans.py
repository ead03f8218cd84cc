"""The readers of the program's spans (`bench/harness/spans.py` and the
``program_span`` metrics built on it), on a synthetic trace with known
spans and device operations, and on the trace recorded on a TPU v5e.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

from __future__ import annotations

import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from bench.harness import spans, xplane  # noqa: E402
from bench.harness.core import load_json, load_module  # noqa: E402

READERS = ["schedule_build_ms.leave", "sync_idle_ms.leave",
           "schedule_build_ms.serve", "batch_idle_ms.serve"]


def _reader(name):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"))


def _ev(name, a, b):
    return xplane.Event(name, a, b - a)


def _trace():
    """A 1 s window.  Device 0 is busy in [0, .10], [.15, .30], [.40, .42],
    [.50, .90] and [.95, 1].  Two whole replays and two whole batches, and
    one of each cut by the window's edge (`xplane.load` clips them to it),
    which would move every median if it were counted."""
    busy = [(0.0, 0.10), (0.15, 0.20), (0.18, 0.30), (0.40, 0.42),
            (0.50, 0.90), (0.95, 1.0)]
    host = [
        # leave: replay schedule builds 70 and 20 ms; device idle in the
        # host syncs 30 + 50 = 80 and 30 ms
        _ev("bench.replay", 0.05, 0.45),
        _ev("replay.schedule_build", 0.05, 0.12),
        _ev("replay.host_sync", 0.12, 0.16),
        _ev("replay.host_sync", 0.30, 0.35),
        _ev("bench.replay", 0.46, 0.98),
        _ev("replay.schedule_build", 0.46, 0.48),
        _ev("replay.host_sync", 0.90, 0.93),
        _ev("bench.replay", 0.0, 0.04),            # cut at the start
        _ev("replay.schedule_build", 0.0, 0.03),   # cut at the start
        _ev("replay.host_sync", 0.01, 0.02),
        # serve: schedule builds 30 + 20 = 50 and 30 ms a batch; device
        # idle 50 + 100 + 30 = 180 and 40 + 50 = 90 ms a batch
        _ev("serve.batch", 0.10, 0.45),
        _ev("online.schedule_build", 0.10, 0.13),
        _ev("online.schedule_build", 0.31, 0.33),
        _ev("serve.batch", 0.46, 0.97),
        _ev("online.schedule_build", 0.46, 0.49),
        _ev("serve.batch", 0.98, 1.0),              # cut at the end
        _ev("online.schedule_build", 0.98, 0.99),
        _ev("bench.window", 0.0, 1.0),
    ]
    return xplane.Trace(ops={0: [_ev("op", a, b) for a, b in busy]},
                        host=host, window=(0.0, 1.0))


def test_host_spans_drop_spans_cut_by_the_window():
    tr = _trace()
    got = spans.host_spans(tr, "bench.replay")
    assert [(e.start, e.end) for e in got] == [(0.05, 0.45), (0.46, 0.98)]
    assert spans.host_spans(tr, "bench.window") == []
    assert [e.start for e in spans.host_spans(tr, "serve.batch")] == \
        [0.10, 0.46]


def test_device_idle_inside_intervals():
    tr = _trace()
    got = spans.device_idle_s(tr, [(0.12, 0.16), (0.10, 0.45), (0.0, 1.0),
                                   (0.42, 0.50), (0.55, 0.60)])
    assert got == pytest.approx([0.03, 0.18, 0.05 + 0.10 + 0.08 + 0.05,
                                 0.08, 0.0])


@pytest.mark.parametrize("name,want", [("schedule_build_ms.leave", 45.0),
                                       ("sync_idle_ms.leave", 55.0),
                                       ("schedule_build_ms.serve", 40.0),
                                       ("batch_idle_ms.serve", 135.0)])
def test_reader_on_known_spans(name, want):
    assert _reader(name).read(SimpleNamespace(trace=_trace())) == \
        pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_without_its_spans(name):
    """The recorded v5e trace has device operations but no program spans
    (a program that does not emit them); a CPU trace has no device."""
    rec = xplane.load(os.path.join(HERE, "data", "small.xplane.pb"))
    assert rec.devices == [0]
    reader = _reader(name)
    assert reader.read(SimpleNamespace(trace=rec)) is None
    no_device = _trace()
    no_device.ops = {}
    assert reader.read(SimpleNamespace(trace=no_device)) is None
    assert reader.read(SimpleNamespace(trace=None)) is None


def test_readers_are_declared_as_program_spans():
    per_layer = {m["name"]: m for m in
                 load_json(ROOT, "BENCHMARK.json")["per_layer"]}
    for name in READERS:
        m = per_layer[name]
        assert m["source"] == "program_span" and m["unit"] == "ms"
        assert m["workloads"] == (["mlp-leave-group-out"]
                                  if name.endswith(".leave")
                                  else ["mlp-serve-poisson"])
