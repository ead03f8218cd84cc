"""The LM cell's per-layer readers on a synthetic trace: each reads the
number its docstring defines from events named as the TPU's trace names
them (the op names are those of the cell's programs compiled for a v5e),
and reads nothing from a trace that lacks them.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench.harness import xplane  # noqa: E402
from bench.harness.core import Run, find_cell, load_module  # noqa: E402

CELL = "lm-leave-group-out-4chip"
KERNEL = "%dequant_deltagrad_update.7 = f32[23136,2048]{1,0} custom-call()"


def _reader(name):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"))


def _trace():
    """Two devices over a 10 s window: busy 0-4 s and 5-8 s, of which an
    all-reduce 1-2 s, one dequant_update call 3-3.5 s and an explicit-step
    program 5-7 s; one replay on the host (0.5-9 s) with a 20 ms schedule
    build, a prefetch wait 4-5.5 s (1 s of it idle), a host sync 4.2-4.7 s
    (all of it idle) and two window stages of 0.5 s."""
    E = xplane.Event
    ops = [E("%fusion.1 = f32[4,1024,2048]", 0.0, 4.0),
           E("%all-reduce.89 = f32[4,1024,2048]", 1.0, 1.0),
           E(KERNEL, 3.0, 0.5),
           E("%fusion.2 = f32[8,2048,2048]", 5.0, 3.0)]
    host = [E("bench.replay", 0.5, 8.5),
            E("replay.schedule_build", 0.6, 0.02),
            E("store.prefetch_wait", 4.0, 1.5),
            E("replay.host_sync", 4.2, 0.5),
            E("store.window_stage", 1.0, 0.5),
            E("store.window_stage", 6.0, 0.5)]
    mods = [E("jit__explicit_step", 5.0, 2.0)]
    return xplane.Trace(ops={0: list(ops), 1: list(ops)},
                        modules={0: list(mods), 1: list(mods)},
                        host=host, window=(0.0, 10.0))


def _run(trace):
    r = Run(cell=find_cell(CELL), seed=1, seconds=10.0, t_start=0.0)
    r.trace = trace
    r.data.update(device_kind="TPU v5 lite", replay_rows=[4], n_rows=160,
                  staged_bytes_setup=0.0)
    return r


def test_device_readers():
    r = _run(_trace())
    assert _reader("idle_share.lm4").read(r) == pytest.approx(30.0)
    assert _reader("collective_share.lm4").read(r) == pytest.approx(
        100.0 / 7.0)
    bytes_ = 21 * 47382528  # f32 w, bv, gc, base, out; int8 q
    assert _reader("dequant_update_roofline.lm4").read(r) == pytest.approx(
        100.0 * 2 * bytes_ / 819e9 / 1.0)
    mfu = _reader("mfu.lm4").read(r)
    assert 0.0 < mfu < 100.0
    assert _reader("explicit_share.lm4").read(r) == pytest.approx(
        100.0 * 2.0 / 7.0)


def test_span_and_counter_readers(monkeypatch):
    from repro.obs import metrics

    reg = metrics.MetricsRegistry()
    reg.counter("store.staged_bytes", unit="B").inc(2.0 * 2**30)
    monkeypatch.setattr(metrics, "_REGISTRY", reg, raising=False)
    monkeypatch.setattr(metrics, "get_registry", lambda: reg)
    r = _run(_trace())
    assert _reader("prefetch_wait_ms.lm4").read(r) == pytest.approx(1000.0)
    assert _reader("stage_gib_per_s.lm4").read(r) == pytest.approx(2.0)
    assert _reader("schedule_build_ms.lm4").read(r) == pytest.approx(20.0)
    assert _reader("sync_idle_ms.lm4").read(r) == pytest.approx(500.0)


@pytest.mark.parametrize("name", [
    "mfu.lm4", "collective_share.lm4", "dequant_update_roofline.lm4",
    "prefetch_wait_ms.lm4", "stage_gib_per_s.lm4", "idle_share.lm4",
    "explicit_share.lm4", "schedule_build_ms.lm4", "sync_idle_ms.lm4"])
def test_readers_find_nothing_in_an_empty_trace(name):
    r = _run(xplane.Trace())
    r.data.clear()
    assert _reader(name).read(r) is None
