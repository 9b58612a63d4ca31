"""Compile the chip's hot path for a described TPU v5e (no chip attached).

The TPU compiler is installed with JAX and compiles for a topology that is
described rather than attached, so these tests catch what interpret mode
cannot: tiles the chip's layout refuses, casts its vector unit lacks, and
programs that do not fit its 16 GB of HBM.  Nothing runs; a compile that
passes says nothing about results or times.

The topology is described inside a module-scoped fixture, never while the
module is imported: only one process may hold the TPU library, and every
test worker imports this file.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.compiled import q4_blocks
from repro.kernels.int8_gemm import int8_gemm_pallas
from repro.kernels.q4_matmul import q4_matmul_pallas, q4_matmul_pallas_db
from repro.models import abstract_params, init_slot_state
from repro.quant.q4 import q4_0_abstract
from repro.serving.engine import step_programs

# granite-8b projections (K -> N): attention q/o, MLP up, MLP down, LM head
GRANITE_SHAPES = [(4096, 4096), (4096, 14336), (14336, 4096), (4096, 49152)]
SLOTS, MAX_SEQ = 16, 2048
# the benchmark's granite-8b.chat cell: 16 layers, 32 slots x 1024 positions
CELL_LAYERS, CELL_SLOTS, CELL_SEQ = 16, 32, 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """Give every ShapeDtypeStruct leaf of ``tree`` the chip's sharding."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("kernel", [q4_matmul_pallas, q4_matmul_pallas_db],
                         ids=["plain", "double_buffered"])
@pytest.mark.parametrize("k,n", GRANITE_SHAPES)
def test_q4_kernel_compiles(one_chip, kernel, k, n):
    x = jax.ShapeDtypeStruct((SLOTS, k), jnp.bfloat16, sharding=one_chip)
    qw = _on(one_chip, q4_0_abstract(n, k))
    fn = jax.jit(lambda x, qw: kernel(x, qw, blocks=q4_blocks(k),
                                      interpret=False))
    hlo = fn.lower(x, qw).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("k,n", GRANITE_SHAPES)
def test_int8_gemm_compiles(one_chip, k, n):
    a = jax.ShapeDtypeStruct((128, k), jnp.uint8, sharding=one_chip)
    w = jax.ShapeDtypeStruct((n, k), jnp.int8, sharding=one_chip)
    fn = jax.jit(lambda a, w: int8_gemm_pallas(a, w, interpret=False))
    hlo = fn.lower(a, w).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_engine_decode_step_compiles(one_chip):
    """The plain engine decode step at granite-8b widths, 2 layers, 16
    slots, 2048 positions: compiles for one v5e and fits its HBM."""
    cfg = dataclasses.replace(get_config("granite-8b"), n_layers=2)
    params = _on(one_chip, abstract_params(cfg))
    state = _on(one_chip, jax.eval_shape(
        lambda: init_slot_state(cfg, SLOTS, MAX_SEQ)))
    tok = jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    _, _, decode = step_programs(cfg)
    compiled = decode.lower(params, tok, state, pos).compile()
    mem = compiled.memory_analysis()
    kv_bytes = 2 * 2 * SLOTS * cfg.n_kv_heads * MAX_SEQ * cfg.hd * 2
    assert mem.argument_size_in_bytes > kv_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def test_engine_decode_appends_to_cache_in_place(one_chip):
    """The plain engine decode step at the chat cell's shapes: the layer
    scan carries the donated KV stack and appends each layer's rows into
    it, so the compiled step holds no second copy of the cache (today's
    temporaries are well under 1% of it) and copies no stacked buffer."""
    cfg = dataclasses.replace(get_config("granite-8b"), n_layers=CELL_LAYERS)
    params = _on(one_chip, abstract_params(cfg))
    state = _on(one_chip, jax.eval_shape(
        lambda: init_slot_state(cfg, CELL_SLOTS, CELL_SEQ)))
    tok = jax.ShapeDtypeStruct((CELL_SLOTS, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((CELL_SLOTS,), jnp.int32, sharding=one_chip)
    _, _, decode = step_programs(cfg)
    compiled = decode.lower(params, tok, state, pos).compile()
    mem = compiled.memory_analysis()
    stack = (CELL_LAYERS, CELL_SLOTS, cfg.n_kv_heads, CELL_SEQ, cfg.hd)
    kv_bytes = 2 * 2 * CELL_LAYERS * CELL_SLOTS * cfg.n_kv_heads * CELL_SEQ * cfg.hd
    assert mem.alias_size_in_bytes >= kv_bytes
    assert mem.temp_size_in_bytes < 0.1 * kv_bytes
    shape = ",".join(map(str, stack))
    copies = re.findall(rf"= bf16\[{shape}\]\S* copy\(", compiled.as_text())
    assert not copies
