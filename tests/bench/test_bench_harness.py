"""The harness's loop and its last line at a tiny size on the CPU, and the
entry point's refusal to run anywhere but on a listed TPU."""

import json
import os
import shutil
import subprocess
import sys
import time
import types

import pytest
from conftest import PEAKS, REPO, SPEC, run_tiny, tiny_cell

from bench import harness

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}


def _check_line(res, cell):
    line = json.loads(json.dumps(res))
    assert RESULT_KEYS <= set(line)
    assert list(line)[-1] == "compared"
    assert line["device"]["platform"] == "cpu"
    assert {"kind", "count", "memory_peak_bytes"} <= set(line["device"])
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    assert set(line["metrics"]) == set(units)
    for name, m in line["metrics"].items():
        assert m["unit"] == units[name] and m["value"] > 0
    return line


def test_open_loop_cell():
    cell = tiny_cell()
    res = run_tiny(cell)
    line = _check_line(res, cell)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 5
    assert {m["name"] for m in cell.end_to_end} == set(line["metrics"])
    assert {"setup_s", "itl_p95_ms", "served_tok_s"} <= set(line["metrics"])
    assert line["compared"]["widest_gap"]["limit"] == 0.5


BACKLOG_MIX = dict(arrivals="backlog", count=1000, preroll_s=0.5,
                   prompt=dict(dist="lognormal", median=40, sigma=0.5, min=16,
                               max=90),
                   output=dict(dist="lognormal", median=12, sigma=0.5, min=4,
                               max=30))


def test_backlog_cell():
    """A ChatGLM-like tiny shape (two KV heads, q/k/v bias, rotary on half
    of each head) serving a backlog due before the window: correct, and
    every request the window admitted got its first token."""
    cell = tiny_cell(mix=BACKLOG_MIX)
    assert cell.shape.qkv_bias and cell.shape.rope_fraction == 0.5
    seen, logged = {}, []
    res = harness.run_cell(cell, 2 ** 33 + 17, 1.0, False, peaks=PEAKS,
                           t_start=time.perf_counter(), log=logged.append,
                           on_run=lambda run: seen.update(run=run))
    line = _check_line(res, cell)
    assert line["correct"] and line["failed"] == 0
    run = seen["run"]
    assert len(run.requests) == 1000
    assert not any(run.in_window(tr.due) for tr in run.requests)
    assert all(tr.submit_t < run.t_open for tr in run.requests)
    admitted = [tr for tr in run.requests if run.in_window(tr.lane_t)]
    assert line["attempted"] == len(admitted) > 0
    assert line["metrics"]["served_tok_s"]["value"] > 0
    assert any(len(tr.token_t) > 1 for tr in run.requests)
    left = [tr for tr in run.requests
            if tr.lane_t is None or tr.lane_t > run.t_close]
    assert left, "the backlog emptied: the window saw no full queue"
    assert any(f"backlog left: {len(left)} of 1000 submitted" in m
               for m in logged)


def test_traced_run_reports_per_layer_metrics(tmp_trace):
    cell = tiny_cell()
    res = run_tiny(cell, trace=True, trace_dir=tmp_trace)
    assert res["correct"]
    # host-clock readers find their numbers; the CPU has no device plane,
    # so the device-trace readers return nothing and are left out
    assert set(res["metrics"]) == {"step_mfu"}
    assert res["device"]["window_s"] > 0
    assert len(res["breakdown"]["idle_gaps"]) <= 10


def test_new_cell_reads_its_own_per_layer_entries(tmp_trace):
    """A cell that ``BENCHMARK.json``'s per-layer entries do not list gets
    none of them; entries of its own, ``<metric>.<suffix>``, are read by
    ``<metric>``'s reader, so a new cell needs new entries and no code."""
    cell = tiny_cell(name="tiny.docbatch", mix=BACKLOG_MIX)
    assert cell.per_layer == []
    assert {m["name"] for m in cell.end_to_end} == {"setup_s",
                                                    "served_tok_s"}
    cell.per_layer = [dict(m, name=f"{m['name']}.batch", moves="served_tok_s",
                           workloads=[cell.name]) for m in SPEC["per_layer"]]
    res = run_tiny(cell, trace=True, trace_dir=tmp_trace)
    assert res["correct"]
    # the CPU has no device plane: the host-clock readers find numbers
    assert "step_mfu.batch" in res["metrics"]
    assert set(res["metrics"]) <= {"step_mfu.batch", "queue_wait_ms.batch"}


def test_no_limit_is_not_correct():
    res = run_tiny(tiny_cell(gap_limit=None))
    assert not res["correct"]


def test_step_records_account_for_every_token():
    seen = {}
    run_tiny(tiny_cell(), on_run=lambda run: seen.update(run=run))
    run = seen["run"]
    for s in run.steps:
        assert s.t0 <= s.t1 and s.generated >= len(s.decode_kv)
        assert 0 <= s.prefill_len <= 16
        assert s.prefill_len == 0 or s.prefill_len & (s.prefill_len - 1) == 0
    got = sum(len(tr.token_t) for tr in run.requests)
    assert got == sum(tr.req.n_generated for tr in run.requests
                      if tr.req is not None)


def test_sample_has_the_longest():
    reqs = []
    for i, (p, n) in enumerate([(10, 5), (40, 9), (12, 3), (8, 1)]):
        r = types.SimpleNamespace(prompt_len=p, n_generated=n, request_id=i)
        reqs.append(types.SimpleNamespace(req=r, done_t=1.0))
    reqs.append(types.SimpleNamespace(req=None, done_t=None))
    got = harness.check_sample(reqs, 3, 5)
    assert got[0].request_id == 1 and len(got) == 3
    assert harness.check_sample(reqs, 3, 5) == got


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "granite-8b.chat",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    with pytest.raises(ValueError):
        json.loads(last)


def test_run_py_refuses_the_cpu():
    proc = _run_py(REPO)
    _no_result(proc)
    assert "no TPU" in proc.stderr


def test_run_py_fails_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__", ".*"))
    _no_result(_run_py(tmp_path, {"PYTHONPATH": ""}))


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("devs,why", [
    ([_Dev("tpu", "TPU v9 imaginary")], "no peaks"),
    ([_Dev("gpu", "H100")], "no TPU"),
    ([_Dev("tpu", "TPU v5 lite")], "needs 4 chips"),
])
def test_chip_peaks_refuses(monkeypatch, devs, why):
    import jax

    sys.path.insert(0, str(REPO / "bench"))
    import run as run_py
    monkeypatch.setattr(jax, "devices", lambda: devs)
    with pytest.raises(SystemExit, match=why):
        run_py.chip_peaks(4)


def test_chip_peaks_of_a_listed_tpu(monkeypatch):
    import jax

    sys.path.insert(0, str(REPO / "bench"))
    import run as run_py
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("tpu", "TPU v5 lite")])
    peaks = run_py.chip_peaks(1)
    assert peaks["bf16_flops"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
