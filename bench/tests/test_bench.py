"""Self-tests of the benchmark, on the CPU at small sizes.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

  * the FLOP and byte counts against XLA's own `cost_analysis()`;
  * the trace reduction on a small trace recorded on a TPU v5e;
  * the refusals: no TPU, and a directory holding only the benchmark;
  * a whole run of each cell at a small size, past the look for a chip:
    `correct` is true; and with each fault the cell can have planted in the
    timed path, `correct` is false;
  * the control (the reference at the next lower precision put in the
    program's place) reads above the cell's limit.
"""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench.harness import check, faults, xplane  # noqa: E402
from bench.harness.core import load_json, run  # noqa: E402

CELLS = sorted(w["name"] for w in load_json(ROOT, "BENCHMARK.json")
               ["workloads"])


def _small(cell):
    """The cell's test size: `small/<cell>.json` overrides top-level keys
    of its configuration and traffic."""
    return load_json(HERE, "small", cell + ".json")


def _small_run(cell, fault=None, on_done=None, seed=20241017):
    small = _small(cell)
    return run(cell, seed, small["seconds"], False, require_tpu=False,
               overrides=small["overrides"], fault=fault, on_done=on_done)


# -- counts -------------------------------------------------------------------


def test_mlp_grad_flops_match_cost_analysis():
    import jax
    import jax.numpy as jnp

    from bench.counts import mlp as counts

    cfg = load_json(BENCH, "configs", "paper-mlp.json")
    ref = _load_reference("paper-mlp")
    rows = 256
    d, h, c = cfg["d_in"], cfg["hidden"], cfg["classes"]
    p = {"w1": jnp.zeros((d, h)), "b1": jnp.zeros((h,)),
         "w2": jnp.zeros((h, c)), "b2": jnp.zeros((c,))}
    x = jnp.zeros((rows, d))
    y = jnp.zeros((rows,), jnp.int32)
    g = jax.jit(jax.grad(ref._loss), static_argnums=(4, 5))
    ca = g.lower(p, x, y, jnp.ones(rows), 0.0, "highest").compile() \
        .cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    want = rows * counts.grad_flops_per_row(cfg)
    assert abs(ca["flops"] - want) / want < 0.02, (ca["flops"], want)


def test_fused_update_bytes_match_cost_analysis():
    import jax
    import jax.numpy as jnp

    from bench.counts import kernels

    n = 238_510
    c = kernels.fused_update(n)
    p = -(-n // kernels.TILE) * kernels.TILE
    v = jnp.zeros((p,))

    def upd(w, g, b, gc, lr, B, dB, s):  # the kernel's arithmetic, in XLA
        return w - lr * (B * (g + b) - s * dB * gc) / jnp.maximum(
            B - s * dB, 1.0)

    ca = jax.jit(upd).lower(v, v, v, v, 0.1, 10.0, 1.0, 1.0).compile() \
        .cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    assert abs(ca["bytes accessed"] - c["bytes"]) / c["bytes"] < 0.01
    assert c["flops"] >= 0.5 * ca["flops"]


# -- trace reduction ----------------------------------------------------------


def test_xplane_reduction_on_recorded_trace():
    """`small.xplane.pb` was recorded on a TPU v5e: inside the span
    `bench.window`, three rounds of the `fused_update` kernel on 238,510
    floats, a 2 ms host sleep under `bench.host_pause`, and a small jitted
    program."""
    from bench.harness.core import load_module

    tr = xplane.load(os.path.join(HERE, "data", "small.xplane.pb"))
    assert tr.devices == [0]
    assert 0.006 < tr.window_s < 0.1
    ops = tr.ops[0]
    reader = load_module(os.path.join(BENCH, "metrics",
                                      "fused_update_roofline.leave.py"))
    assert xplane.count_by_name(ops, reader.KERNEL) == 3
    assert xplane.time_by_name(ops, reader.KERNEL) == pytest.approx(
        3 * 87.9e-6, rel=0.01)
    assert xplane.count_by_name(tr.modules[0], r"^jit_update") == 3
    busy = xplane.busy_s(tr, 0)
    assert 0 < busy < tr.window_s
    gaps = xplane.idle_gaps(tr, k=10**6)
    assert gaps[0][0].startswith("bench.host_pause")
    assert gaps[0][1] >= 0.002
    assert sum(g[1] for g in gaps) == pytest.approx(tr.window_s - busy,
                                                     rel=1e-9)
    top = xplane.top_ops(tr)
    assert top[0][0] == "%deltagrad_update.1 = f32[1,238592]"
    assert top[0][1] == pytest.approx(3 * 87.9e-6, rel=0.01)


def test_union_and_busy():
    assert xplane.union([(0, 1), (0.5, 2), (3, 4)]) == [(0, 2), (3, 4)]


# -- refusals -----------------------------------------------------------------


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_refuses_in_a_bare_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout


# -- whole runs, faults and the control ---------------------------------------


@pytest.mark.parametrize("cell", CELLS)
def test_small_run_is_correct(cell):
    out = _small_run(cell)
    assert out["correct"], out["compared"]
    assert list(out)[-1] == "compared"
    assert out["device"]["platform"] == "cpu"
    assert out["failed"] == 0 and out["attempted"] > 0


def test_small_traced_run_is_correct():
    """On the CPU the trace has no TPU plane: no device metric is read,
    and the run is still whole and checked."""
    cell = "mlp-leave-group-out"
    small = _small(cell)
    out = run(cell, 5, small["seconds"], True, require_tpu=False,
              overrides=small["overrides"])
    assert out["correct"], out["compared"]
    assert out["metrics"] == {}
    assert out["device"]["busy_s"] == 0.0
    assert out["breakdown"] == {"device_ops": [], "idle_gaps": []}


@pytest.mark.parametrize("fault", faults.FAULTS)
@pytest.mark.parametrize("cell", CELLS)
def test_planted_fault_is_not_correct(cell, fault):
    out = _small_run(cell, fault=fault)
    assert not out["correct"], (fault, out["compared"])


def test_refused_request_is_not_correct(monkeypatch):
    """A request the serving tier refuses is counted failed, and a run with
    one is not correct, however well the rest unlearn."""
    from repro.serve import RetryAfter, ServingScheduler

    submit, calls = ServingScheduler.submit, []

    def refuse_third(self, *a, **kw):
        calls.append(1)
        if len(calls) == 3:
            raise RetryAfter("planted", 0.1)
        return submit(self, *a, **kw)

    monkeypatch.setattr(ServingScheduler, "submit", refuse_third)
    out = _small_run("mlp-serve-poisson")
    assert out["failed"] == 1
    assert out["compared"]["failed_requests"] == {"value": 1, "limit": 0}
    assert out["compared"]["unlearn_gap"]["value"] \
        <= out["compared"]["unlearn_gap"]["limit"]
    assert not out["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limit(cell):
    """The control's answers, put through the run's own comparison in the
    program's place, are not correct: the unlearning gap fails."""
    got = {}

    def on_done(r, compared):
        ctl = check.control_answers(r, r.cell.limits["control"])
        got["program"] = compared
        got["control"] = check.compare(r, answers=ctl)

    _small_run(cell, on_done=on_done)
    assert all(c["ok"] for c in got["program"]), got
    gap = {c["name"]: c for c in got["control"]}["unlearn_gap"]
    assert np.isfinite(gap["value"]) and not gap["ok"], got


def _load_reference(name):
    from bench.harness.core import load_module
    return load_module(os.path.join(BENCH, "configs",
                                    name + ".reference.py"))


def test_small_sizes_are_listed():
    """Every cell has its own test size, a file found by the cell's name."""
    missing = [c for c in CELLS
               if not os.path.isfile(os.path.join(HERE, "small",
                                                  c + ".json"))]
    assert CELLS and not missing, missing
