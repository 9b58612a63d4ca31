"""Tiny cells for the benchmark's CPU tests: the harness, reference and
check at sizes a test run holds."""

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import harness  # noqa: E402
from bench.model import Shape  # noqa: E402

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def tiny_shape(**kw) -> Shape:
    base = dict(name="tiny", d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
                head_dim=16, d_ff=128, vocab=256, rope_theta=10000.0,
                rope_fraction=0.5, qkv_bias=True, eps=1e-5)
    base.update(kw)
    return Shape(**base)


CHAT_MIX = dict(arrivals="poisson", rate=20.0, preroll_s=0.5,
                prompt=dict(dist="lognormal", median=24, sigma=0.8, min=4,
                            max=60),
                output=dict(dist="lognormal", median=12, sigma=0.7, min=2,
                            max=30))


def tiny_cell(name="granite-8b.chat", mix=CHAT_MIX, shape=None,
              **engine) -> harness.Cell:
    """A cell of ``BENCHMARK.json``'s metrics with a tiny model and engine."""
    eng = dict(slots=4, max_seq=128, prefill_chunk=16, check_requests=3,
               gap_limit=0.5, trace_seconds=0.3)
    eng.update(engine)
    return harness.Cell(
        name=name, chips=1, shape=shape or tiny_shape(), reference="decoder",
        mix=dict(mix), engine=eng,
        end_to_end=[m for m in SPEC["end_to_end"]
                    if harness._reports(m, name)],
        per_layer=[m for m in SPEC["per_layer"] if harness._reports(m, name)])


def run_tiny(cell, seed=2 ** 33 + 5, seconds=1.0, trace=False, **kw):
    import time

    return harness.run_cell(cell, seed, seconds, trace, peaks=PEAKS,
                            t_start=time.perf_counter(), log=lambda *a: None,
                            **kw)


@pytest.fixture
def tmp_trace(tmp_path):
    return tmp_path / "trace"


_PROGRAMS = {}


@pytest.fixture(autouse=True)
def shared_step_programs(monkeypatch):
    """Engines of one configuration share their jitted step programs, so
    that tiny runs after the first compile nothing."""
    from repro.serving import engine as engine_mod

    build = engine_mod.step_programs

    def cached(cfg, *, trunk=None, apply_head=True, donate_state=True):
        if trunk is not None:
            return build(cfg, trunk=trunk, apply_head=apply_head,
                         donate_state=donate_state)
        key = (cfg, apply_head, donate_state)
        if key not in _PROGRAMS:
            _PROGRAMS[key] = build(cfg, apply_head=apply_head,
                                   donate_state=donate_state)
        return _PROGRAMS[key]

    monkeypatch.setattr(engine_mod, "step_programs", cached)
