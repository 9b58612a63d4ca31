"""chatglm3-6b in the benchmark: a ChatGLM-shaped tiny model served through
the harness and held to the plain reference, with two planted faults;
ChatGLM's rotary pinned by hand, outside both implementations; the
benchmark's configuration tied to the registry's and to one chip's memory;
the cell's readers on a traced run of it kept from a v5e; and the
``prefill_roofline`` reader."""

import json
import time

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import PEAKS, REPO, tiny_cell, tiny_shape
from test_bench_trace import _edge_run

from bench import flops, harness, xtrace
from bench.model import load_config
from bench.references import decoder

CELL = "chatglm3-6b.docbatch"

# two K/V heads shared by eight query heads each, rotary on half of each
# head, q/k/v biases (bench/model.py draws them non-zero), an untied head
SHAPE = tiny_shape(d_model=128, n_heads=16, n_kv_heads=2, head_dim=16,
                   d_ff=256, vocab=512, tied=False)
# prompts of 3 to 7 pieces of 16, so that later pieces attend at depth;
# max_seq at least twice the longest prompt
MIX = dict(arrivals="backlog", count=400, preroll_s=0.5,
           prompt=dict(dist="lognormal", median=64, sigma=0.4, min=48,
                       max=110),
           output=dict(dist="lognormal", median=12, sigma=0.5, min=4,
                       max=24))
LIMIT = 0.5
# The engine serves bf16 (f32 softmax) against the f32 reference: its
# widest gap read 0 to 0.032 over 8 seeds on the CPU.  A fifth of the limit
# leaves room for rounding and none for a wrong mechanism: the faults below
# read 3.3 (v bias dropped) and 7.0 (tied head) on the same cell.
PROGRAM_GAP = LIMIT / 5


def _cell(check_requests=6):
    return tiny_cell(name="tiny.docbatch", mix=MIX, shape=SHAPE,
                     max_seq=256, prefill_chunk=16, gap_limit=LIMIT,
                     check_requests=check_requests)


def _run(seed, **kw):
    seen = {}
    res = harness.run_cell(_cell(kw.pop("check_requests", 6)), seed, 1.0,
                           False, peaks=PEAKS, t_start=time.perf_counter(),
                           log=lambda *a: None,
                           on_run=lambda run: seen.update(run=run), **kw)
    return res, seen["run"]


@pytest.mark.parametrize("seed", [2 ** 33 + 17, 2 ** 40 + 3])
def test_tiny_chatglm_backlog_matches_the_reference(seed):
    res, run = _run(seed)
    assert res["correct"] and res["failed"] == 0, res["compared"]
    assert res["compared"]["widest_gap"]["value"] <= PROGRAM_GAP
    # later pieces attended at depth: a piece started two pieces in
    assert any(s.prefill_start >= 32 for s in run.steps)
    assert all(tr.item.prompt.size >= 48 for tr in run.requests)


def _head_tied(engine):
    """The engine reads the embedding, transposed, as its head."""
    p = engine.params
    engine.params = dict(p, embed=dict(p["embed"], out=p["embed"]["tok"].T))


def _bias_dropped(engine):
    """The engine's q, k and v biases are zero, as ``init_attn`` makes
    them."""
    p = engine.params
    mixer = {k: (jnp.zeros_like(v) if k in ("bq", "bk", "bv") else v)
             for k, v in p["period"][0]["mixer"].items()}
    engine.params = dict(p, period=[dict(p["period"][0], mixer=mixer)])


@pytest.mark.parametrize("fault", [_head_tied, _bias_dropped],
                         ids=["head-tied", "qkv-bias-dropped"])
def test_planted_fault_is_not_correct(fault):
    res, _ = _run(2 ** 33 + 17, check_requests=64, engine_hook=fault)
    assert not res["correct"]
    assert res["compared"]["widest_gap"]["value"] > LIMIT


# ------------------------------------------------------------- rotary ---
def _chatglm_rope(x, pos):
    """ChatGLM's published rule at head_dim 128, written out in float64:
    dims 0-63 rotate in pairs (2i, 2i + 1) by pos * 10000^(-2i/64), dims
    64-127 pass unchanged.  x (T, H, 128), pos (T,)."""
    x = np.asarray(x, np.float64)
    out = x.copy()
    for t, p in enumerate(pos):
        for i in range(32):
            a = p * 10000.0 ** (-2 * i / 64)
            c, s = np.cos(a), np.sin(a)
            x0, x1 = x[t, :, 2 * i], x[t, :, 2 * i + 1]
            out[t, :, 2 * i] = x0 * c - x1 * s
            out[t, :, 2 * i + 1] = x1 * c + x0 * s
    return out


POS = np.array([0, 1, 7, 511, 4097, 8191])


def _reference_rope(x):
    return decoder._rope(jnp.asarray(x), jnp.asarray(POS), 64, 10000.0)


def _engine_rope(x):
    from repro.models.layers import apply_rope

    y = apply_rope(jnp.asarray(x).transpose(1, 0, 2)[None],
                   jnp.asarray(POS), theta=10000.0, fraction=0.5)
    return y[0].transpose(1, 0, 2)


@pytest.mark.parametrize("rope", [_reference_rope, _engine_rope],
                         ids=["reference", "engine"])
def test_rope_is_chatglms(rope):
    x = np.random.default_rng(3).standard_normal((len(POS), 4, 128))
    x = x.astype(np.float32)
    want = _chatglm_rope(x, POS)
    got = np.asarray(rope(x), np.float64)
    np.testing.assert_array_equal(got[..., 64:], x[..., 64:])
    # angles are taken in float32: at position 8191 the angle is off by up
    # to about 8191 * 2^-24 ~ 5e-4 rad, times |x| under 5; a wrong pairing,
    # base or rotated width is off by order 1
    np.testing.assert_allclose(got, want, atol=4e-3, rtol=0)
    assert np.abs(got[1:, :, :64] - x[1:, :, :64]).max() > 0.5


# -------------------------------------------------------- configuration --
def test_bench_config_is_the_registrys_but_for_depth():
    from repro.configs import get_config

    shape, conf = load_config(REPO / "bench", "chatglm3-6b")
    cfg = get_config("chatglm3-6b")
    assert (shape.d_model, shape.n_heads, shape.n_kv_heads, shape.head_dim,
            shape.d_ff, shape.vocab) == (cfg.d_model, cfg.n_heads,
                                         cfg.n_kv_heads, cfg.hd, cfg.d_ff,
                                         cfg.vocab_size)
    assert (shape.rope_fraction, shape.qkv_bias, shape.rope_theta,
            shape.tied) == (cfg.rope_fraction, cfg.qkv_bias, cfg.rope_theta,
                            cfg.tie_embeddings)
    assert conf["reduced"] == ["num_layers"]
    assert conf["published"]["num_layers"] == cfg.n_layers == 28
    assert shape.n_layers == 16


def test_the_cell_fits_one_chip_with_its_cache_held_twice():
    """Weights 7.59 GB and K/V 2.15 GB at 16 slots x 8192; ``init_state``
    holds the cache twice while it is set up."""
    cell = harness.load_cell(CELL)
    shape, e = cell.shape, cell.engine
    embed = shape.vocab * shape.d_model * flops.BF16
    weights = flops.weight_bytes(shape) + embed
    kv = flops.kv_bytes_per_token(shape) * e["slots"] * e["max_seq"]
    assert flops.kv_bytes_per_token(shape) == shape.n_layers * 1024
    assert weights == pytest.approx(7.59e9, rel=2e-3)
    assert kv == pytest.approx(2.15e9, rel=2e-3)
    hbm = json.loads((REPO / "bench" / "peaks.json").read_text())[
        "TPU v5 lite"]["hbm_bytes"]
    assert weights + 2 * kv < hbm
    # a prompt and its answer fit the slot's cache
    assert cell.mix["prompt"]["max"] + cell.mix["output"]["max"] \
        < e["max_seq"]


# ------------------------------------------------------ prefill_roofline --
KEPT = REPO / "bench" / "testdata" / CELL
KEPT_METRICS = ("prefill_chunk_ms.batch", "decode_step_ms.batch",
                "decode_roofline.batch", "device_idle.batch",
                "prefill_roofline.batch")


def _kept_run():
    """A traced run of the cell on a v5e (seed 2717017701; 16 slots x 8192,
    pieces of 1 to 256 tokens at depths 0 to 4352), with the host's steps
    and the metrics the chip read."""
    kept = json.loads((KEPT / "steps.json").read_text())
    run = harness.Run(
        cell=harness.load_cell(kept["cell"]), peaks=kept["peaks"],
        setup_s=0.0, t_open=0.0, t_close=0.0, requests=[],
        steps=[harness.Step(**s) for s in kept["steps"]],
        trace=xtrace.load(KEPT / "trace.xplane.pb.xz"))
    return run, kept["metrics"]


@pytest.mark.parametrize("name", KEPT_METRICS)
def test_kept_trace_reads_as_on_the_chip(name):
    """Each ``.batch`` entry's reader gives what it gave on the chip
    (``step_idle_ms`` reads the program's spans, which the kept trace's
    loader leaves out)."""
    run, recorded = _kept_run()
    assert harness.reader(name)(run) == pytest.approx(recorded[name],
                                                      rel=1e-12)


@pytest.mark.parametrize("kept", [_kept_run, _edge_run],
                         ids=[CELL, "granite-8b.backlog.edge"])
def test_prefill_roofline_on_the_kept_trace(kept):
    """The reader against a hand computation on two v5e backlog runs: the
    cell's own, whose partial pieces it leaves out, and the granite-8b
    stand-in (16 layers, 6 slots x 8192, pieces of 256 at depths 0 to
    1536)."""
    run, _ = kept()
    pairs = [(st, ex) for st, ex in run.matched("_prefill")
             if st.prefill_len == 256]
    assert len(pairs) >= 5 and max(st.prefill_start for st, _ in pairs)
    shape, peaks = run.cell.shape, run.peaks
    least = 0.0
    for st, _ in pairs:
        ops = flops.prefill_flops(shape, 256, st.prefill_start)
        needed = (flops.weight_bytes(shape) + flops.kv_bytes_per_token(shape)
                  * (st.prefill_start + 256))
        least += max(ops / peaks["bf16_flops"],
                     needed / peaks["hbm_bytes_per_s"])
    busy = sum(ex.end - ex.start for _, ex in pairs) * 1e-9
    got = harness.reader("prefill_roofline")(run)
    assert got == pytest.approx(100 * least / busy, rel=1e-12)
    assert 0 < got <= 100


def test_prefill_roofline_reads_full_pieces_only():
    """A partial piece is left out; with no full piece, nothing is read."""
    MS = 1e6

    def ex(name, start, dur):
        return xtrace.Event(f"jit_{name}(7)", start * MS,
                            (start + dur) * MS, name)

    mods = [ex("_prefill", 2, 40), ex("_prefill", 52, 5)]
    trace = xtrace.Trace(window=(0.0, 100 * MS), modules={0: mods},
                         ops={0: list(mods)}, spans=[])
    cell = harness.load_cell(CELL)
    steps = [harness.Step(0.0, 0.05, prefill_len=256, prefill_start=4096,
                          traced=True),
             harness.Step(0.05, 0.10, prefill_len=64, prefill_start=4352,
                          traced=True)]
    run = harness.Run(cell=cell, peaks=PEAKS, setup_s=1.0, t_open=0.0,
                      t_close=0.1, requests=[], steps=steps, trace=trace)
    shape = cell.shape
    least = max(flops.prefill_flops(shape, 256, 4096) / PEAKS["bf16_flops"],
                (flops.weight_bytes(shape)
                 + flops.kv_bytes_per_token(shape) * (4096 + 256))
                / PEAKS["hbm_bytes_per_s"])
    assert harness.reader("prefill_roofline")(run) == pytest.approx(
        100 * least / 0.040)
    run.steps[0].prefill_len = 128
    assert harness.reader("prefill_roofline")(run) is None
