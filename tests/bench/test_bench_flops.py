"""FLOP and byte functions against numbers worked out by hand, for the
benchmark's configuration and for one with QKV bias and two KV groups
(ChatGLM3-6B's widths); 16 layers each."""

import pytest
from conftest import REPO

from bench import flops
from bench.model import Shape, load_config


@pytest.fixture(scope="module")
def granite():
    return load_config(REPO / "bench", "granite-8b")[0]


@pytest.fixture(scope="module")
def chatglm():
    return Shape(name="chatglm3-6b", d_model=4096, n_layers=16, n_heads=32,
                 n_kv_heads=2, head_dim=128, d_ff=13696, vocab=65024,
                 rope_theta=10000.0, rope_fraction=0.5, qkv_bias=True,
                 eps=1e-5)


def test_shapes_read_from_the_published_keys(granite, chatglm):
    assert (granite.d_model, granite.n_layers, granite.n_heads,
            granite.n_kv_heads, granite.head_dim, granite.d_ff,
            granite.vocab) == (4096, 16, 32, 8, 128, 14336, 49152)
    assert (chatglm.d_model, chatglm.n_layers, chatglm.n_heads,
            chatglm.n_kv_heads, chatglm.head_dim, chatglm.d_ff,
            chatglm.vocab) == (4096, 16, 32, 2, 128, 13696, 65024)
    assert granite.rot_dim == 128 and chatglm.rot_dim == 64
    assert not granite.qkv_bias and granite.tied


def test_granite_weights_and_cache(granite):
    # 4096*(4096+2*1024) + 4096*4096 + 3*4096*14336
    assert flops.linear_params(granite) == 218103808
    # 16 layers bf16 + 33 norms f32 + head bf16 = 7.38 GB (+0.40 GB of
    # embedding gathered per token, left out)
    assert flops.weight_bytes(granite) == (16 * 218103808 * 2
                                           + 33 * 4096 * 4
                                           + 4096 * 49152 * 2)
    assert flops.weight_bytes(granite) == 7382515712
    assert flops.kv_bytes_per_token(granite) == 16 * 4096   # 4 KiB a layer


def test_chatglm_weights_and_cache(chatglm):
    # 4096*(4096+2*256) + 4096*4096 + 3*4096*13696
    assert flops.linear_params(chatglm) == 203948032
    assert flops.weight_bytes(chatglm) == (16 * 203948032 * 2
                                           + 16 * (4096 + 512) * 2
                                           + 33 * 4096 * 4
                                           + 4096 * 65024 * 2)
    assert flops.weight_bytes(chatglm) == 7059701760
    assert flops.kv_bytes_per_token(chatglm) == 16 * 1024   # 1 KiB a layer


def test_decode(granite, chatglm):
    # one row at 100 positions: trunk 2*16*218103808, head 2*4096*49152,
    # attention 16 layers * 4 * 4096 * 100
    assert flops.decode_flops(granite, [100]) == 7408189440
    assert flops.decode_flops(granite, [100, 300]) == (
        2 * 7408189440 + 16 * 4 * 4096 * 200)
    assert flops.decode_bytes(chatglm, [1000, 24]) == (
        7059701760 + 16384 * 1024)
    assert flops.decode_flops(granite, []) == 0


def test_prefill(granite, chatglm):
    # 256 tokens from 0: 2*16*218103808*256 + 16*4*4096*(256*257/2) + head
    assert flops.prefill_flops(granite, 256, 0) == 1795732537344
    # after 1024 cached tokens the attention pairs grow by 256*1024
    assert (flops.prefill_flops(chatglm, 256, 1024)
            - flops.prefill_flops(chatglm, 256, 0)) == 16 * 4 * 4096 * 262144
