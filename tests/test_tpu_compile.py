"""The main path's Pallas kernels compile for a TPU v5e chip.

Interpret mode (every other kernel test) accepts block shapes, scratch
layouts and in-kernel ops that the TPU compiler refuses. These tests
compile each kernel with ``interpret=False`` for one chip of a described
(not attached) v5e, at stablelm-1.6b widths (32 MHA heads, head dim 64),
and ``gla_scan`` at xlstm-350m's mLSTM widths (qk head dim 256, v head dim
512, chunk 64). Nothing runs: they check that the compiler accepts the
kernels and that the program holds a Mosaic call.

The topology is described inside a module-scoped fixture, never at import
time, so every test worker collects the same tests and only the worker
given this file loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops

H, D = 32, 64                     # stablelm-1.6b: MHA, head dim 64


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _flash_fwd(q, k, v):
    return ops.flash_attention(q, k, v, causal=True, interpret=False)


def _flash_fwd_bwd(q, k, v):
    def loss(q, k, v):
        return _flash_fwd(q, k, v).astype(jnp.float32).sum()
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("seq", [2048, 16], ids=["seq2048", "chunk16"])
@pytest.mark.parametrize("fn", [_flash_fwd, _flash_fwd_bwd],
                         ids=["fwd", "fwd_bwd"])
def test_flash_attention_compiles(one_chip, fn, seq):
    qkv = ((2, seq, H, D), jnp.bfloat16)
    _compile(fn, one_chip, qkv, qkv, qkv)


def test_decode_attention_compiles(one_chip):
    B, T = 4, 4096
    _compile(lambda q, k, v, n: ops.decode_attention(q, k, v, n,
                                                     interpret=False),
             one_chip, ((B, 1, H, D), jnp.bfloat16),
             ((B, T, H, D), jnp.bfloat16), ((B, T, H, D), jnp.bfloat16),
             ((B,), jnp.int32))


# paged pool [NB+1, KV, bs, hd]: 4 rows x 17 blocks of 16 tokens, + trash
POOL = ((4 * 17 + 1, H, 16, D), jnp.bfloat16)
# chatglm3-6b: 32 query heads of 128 over 2 K/V groups, 128-token blocks
GQA_H, GQA_POOL = 32, ((4 * 17 + 1, 2, 128, 128), jnp.bfloat16)
# stablelm-1.6b at 128-token blocks: a tile too wide for one grid step
MHA_WIDE_POOL = ((4 * 17 + 1, H, 128, D), jnp.bfloat16)


@pytest.mark.parametrize("heads,pool", [(H, POOL), (GQA_H, GQA_POOL),
                                        (H, MHA_WIDE_POOL)],
                         ids=["mha", "gqa", "mha_wide"])
def test_paged_decode_attention_compiles(one_chip, heads, pool):
    _compile(lambda q, kp, vp, t, n: ops.paged_decode_attention(
                 q, kp, vp, t, n, interpret=False),
             one_chip, ((4, 1, heads, pool[0][-1]), jnp.bfloat16), pool,
             pool, ((4, 17), jnp.int32), ((4,), jnp.int32))


def test_paged_prefill_attention_compiles(one_chip):
    _compile(lambda q, kp, vp, t, s, n: ops.paged_prefill_attention(
                 q, kp, vp, t, s, n, interpret=False),
             one_chip, ((4, 64, H, D), jnp.bfloat16), POOL, POOL,
             ((4, 17), jnp.int32), ((4,), jnp.int32), ((4,), jnp.int32))


def test_gla_scan_fwd_bwd_compiles(one_chip):
    B, S, Hs, dk, dv = 1, 2048, 4, 256, 512    # xlstm-350m mLSTM

    def fwd_bwd(q, k, v, g):
        def loss(q, k, v, g):
            y = ops.gla_scan(q, k, v, g, chunk=64, interpret=False)
            return y.astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2, 3))(q, k, v, g)

    text = _compile(fwd_bwd, one_chip, ((B, S, Hs, dk), jnp.bfloat16),
                    ((B, S, Hs, dk), jnp.bfloat16),
                    ((B, S, Hs, dv), jnp.bfloat16), ((B, S, Hs), jnp.float32))
    assert text.count("tpu_custom_call") >= 2       # fwd + reverse scan
