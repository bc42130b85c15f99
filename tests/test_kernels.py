"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU): shape/dtype
sweeps + hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref
from repro.models.attention import blockwise_attention


def _mk(key, shape, dt):
    return jax.random.normal(key, shape, dt)


FLASH_CASES = [
    # B, Sq, Sk, H, KV, dh, causal, window, bq, bk, dtype
    (2, 128, 128, 4, 2, 32, True, 0, 64, 64, jnp.float32),
    (1, 100, 100, 4, 4, 16, True, 0, 32, 32, jnp.float32),
    (2, 64, 64, 8, 2, 64, True, 30, 32, 32, jnp.bfloat16),
    (1, 128, 128, 2, 1, 32, False, 0, 64, 64, jnp.float32),
    (1, 96, 160, 4, 1, 16, False, 0, 32, 64, jnp.float32),
    (2, 128, 128, 4, 2, 32, True, 64, 128, 128, jnp.float32),
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_vs_ref(case):
    B, Sq, Sk, H, KV, dh, causal, window, bq, bk, dt = case
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _mk(ks[0], (B, Sq, H, dh), dt)
    k = _mk(ks[1], (B, Sk, KV, dh), dt)
    v = _mk(ks[2], (B, Sk, KV, dh), dt)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              bq=bq, bk=bk, interpret=True)
    qh = jnp.moveaxis(q, 2, 1).reshape(B * H, Sq, dh)
    kh = jnp.moveaxis(k, 2, 1).reshape(B * KV, Sk, dh)
    vh = jnp.moveaxis(v, 2, 1).reshape(B * KV, Sk, dh)
    r = ref.flash_attention_ref(qh, kh, vh, causal=causal, window=window,
                                group=H // KV)
    r = jnp.moveaxis(r.reshape(B, H, Sq, dh), 1, 2)
    tol = 2e-2 if dt == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(r, np.float32), atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# Gradient sweeps: jax.grad through the Pallas custom_vjp (dq + dk/dv
# kernels, interpret=True) vs the jnp blockwise VJP vs naive full-matrix
# autodiff — over GQA groups, causal + sliding window, non-block-divisible
# lengths, and bf16.
# ---------------------------------------------------------------------------

def _naive_attention(q, k, v, causal, window):
    """Full-matrix oracle in [B,S,H,dh] layout (plain autodiff reference)."""
    B, Sq, H, dh = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    qh = jnp.moveaxis(q, 2, 1).reshape(B * H, Sq, dh)
    kh = jnp.moveaxis(k, 2, 1).reshape(B * KV, Sk, dh)
    vh = jnp.moveaxis(v, 2, 1).reshape(B * KV, Sk, v.shape[-1])
    r = ref.flash_attention_ref(qh, kh, vh, causal=causal, window=window,
                                group=H // KV)
    return jnp.moveaxis(r.reshape(B, H, Sq, -1), 1, 2)


GRAD_CASES = [
    # B, Sq, Sk, H, KV, dh, causal, window, bq, bk, dtype
    (2, 128, 128, 4, 2, 32, True, 0, 64, 64, jnp.float32),   # GQA, causal
    (1, 100, 100, 4, 4, 16, True, 0, 32, 32, jnp.float32),   # non-divisible
    (1, 96, 160, 4, 1, 16, False, 0, 32, 64, jnp.float32),   # Sq!=Sk, MQA
    (2, 64, 64, 8, 2, 32, True, 30, 32, 32, jnp.float32),    # sliding window
    (2, 128, 128, 4, 2, 32, True, 64, 128, 128, jnp.float32),  # window=block
    (2, 64, 64, 8, 4, 32, True, 0, 32, 32, jnp.bfloat16),    # bf16
]


@pytest.mark.parametrize("case", GRAD_CASES)
def test_flash_attention_grad_vs_references(case):
    B, Sq, Sk, H, KV, dh, causal, window, bq, bk, dt = case
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = _mk(ks[0], (B, Sq, H, dh), dt)
    k = _mk(ks[1], (B, Sk, KV, dh), dt)
    v = _mk(ks[2], (B, Sk, KV, dh), dt)
    do = _mk(ks[3], (B, Sq, H, dh), dt)

    def scal(attn_fn):
        return lambda q, k, v: jnp.sum(
            attn_fn(q, k, v).astype(jnp.float32) * do.astype(jnp.float32))

    g_pallas = jax.grad(scal(lambda q, k, v: ops.flash_attention(
        q, k, v, causal=causal, window=window, bq=bq, bk=bk,
        interpret=True)), argnums=(0, 1, 2))(q, k, v)
    g_block = jax.grad(scal(lambda q, k, v: blockwise_attention(
        q, k, v, causal=causal, window=window, block=bk)),
        argnums=(0, 1, 2))(q, k, v)
    g_naive = jax.grad(scal(lambda q, k, v: _naive_attention(
        q, k, v, causal, window)), argnums=(0, 1, 2))(q, k, v)

    tol = 5e-2 if dt == jnp.bfloat16 else 1e-3
    for name, gp, gb, gn in zip("qkv", g_pallas, g_block, g_naive):
        gp, gb, gn = (np.asarray(g, np.float32) for g in (gp, gb, gn))
        np.testing.assert_allclose(gp, gn, atol=tol, rtol=tol,
                                   err_msg=f"pallas vs naive d{name}")
        np.testing.assert_allclose(gb, gn, atol=tol, rtol=tol,
                                   err_msg=f"blockwise vs naive d{name}")


def test_pallas_backend_train_step_all_rules():
    """One CPU training step through the fused-kernel attention path
    (attn_backend='pallas', interpret) under every update rule."""
    import jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.configs import get_reduced
    from repro.core.trainer import TrainerConfig, init_state, jit_train_step
    from repro.models import init_params
    from repro.optim import sgd_momentum

    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = get_reduced("stablelm-1.6b").with_(attn_backend="pallas")
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = sgd_momentum(0.9)
    key = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(key, (2, 32), 0, cfg.vocab_size),
             "targets": jax.random.randint(key, (2, 32), 0, cfg.vocab_size)}
    for rule in ("dp", "cdp_v1", "cdp_v2"):
        tr = TrainerConfig(rule=rule, lr_schedule=lambda s: 0.05,
                           donate=False)
        state = init_state(cfg, tr, params, opt)
        jitted, _, _ = jit_train_step(cfg, tr, mesh, opt, state, batch)
        state, met = jitted(state, batch)
        assert np.isfinite(float(met["loss"])), rule


DECODE_CASES = [
    (2, 256, 4, 2, 32, 128, jnp.float32),
    (1, 100, 8, 8, 16, 64, jnp.float32),
    (4, 512, 8, 4, 64, 256, jnp.bfloat16),
]


@pytest.mark.parametrize("case", DECODE_CASES)
def test_decode_attention_vs_ref(case):
    B, T, H, KV, dh, bk, dt = case
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _mk(ks[0], (B, 1, H, dh), dt)
    k = _mk(ks[1], (B, T, KV, dh), dt)
    v = _mk(ks[2], (B, T, KV, dh), dt)
    cl = jnp.asarray(np.random.default_rng(0).integers(1, T, B), jnp.int32)
    out = ops.decode_attention(q, k, v, cl, bk=bk, interpret=True)
    qh = q[:, 0].reshape(B * H, dh)
    kh = jnp.moveaxis(k, 2, 1).reshape(B * KV, T, dh)
    vh = jnp.moveaxis(v, 2, 1).reshape(B * KV, T, dh)
    r = ref.decode_attention_ref(qh, kh, vh, jnp.repeat(cl, KV),
                                 group=H // KV).reshape(B, 1, H, dh)
    tol = 3e-2 if dt == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(r, np.float32), atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# decode_attn backend parity: the registry's "pallas" op (flash-decode
# kernel) vs the jnp decode path, through the MODEL layer (gqa_decode /
# mla_decode) across GQA, sliding-window, ring-buffer, and MLA cache cases.
# ---------------------------------------------------------------------------

def test_decode_attention_kernel_window_vs_jnp():
    from repro.models.attention import decode_attention
    B, T, H, KV, dh = 2, 96, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _mk(ks[0], (B, 1, H, dh), jnp.float32)
    k = _mk(ks[1], (B, T, KV, dh), jnp.float32)
    v = _mk(ks[2], (B, T, KV, dh), jnp.float32)
    cl = jnp.asarray([40, 90], jnp.int32)
    for window in (0, 16, 48):
        o_jnp = decode_attention(q, k, v, cl, window=window, backend="jnp")
        o_pal = ops.decode_attention(q, k, v, cl, window=window, bk=32,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_jnp),
                                   atol=2e-5, rtol=2e-5,
                                   err_msg=f"window={window}")


def _decode_parity_cfgs(arch):
    from repro.configs import get_reduced
    from repro.kernels.registry import KernelSpec
    cfg = get_reduced(arch)
    return cfg, cfg.with_(kernels=KernelSpec(decode_attn="pallas"))


@pytest.mark.parametrize("case", [
    # (arch, cache_len, n_tokens) — mixtral reduced has window=64, so its
    # cache is always the window-sized ring (gqa_cache_init clamps T to the
    # window); 20 tokens leave it partially filled, 70 wrap it. The linear
    # windowed cache (window < T) is covered at the kernel level above.
    ("stablelm-1.6b", 24, 10),       # plain GQA
    ("deepseek-v3-671b", 24, 10),    # MLA latent cache
    ("mixtral-8x22b", 96, 20),       # windowed ring, partially filled
    ("mixtral-8x22b", 64, 70),       # windowed ring, wraps
])
def test_decode_backend_parity_model_level(case):
    from repro.models import decode_step, init_cache, init_params
    arch, cache_len, n = case
    cfg_jnp, cfg_pal = _decode_parity_cfgs(arch)
    params = init_params(cfg_jnp, jax.random.PRNGKey(0))
    B = 2
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, n), 0,
                              cfg_jnp.vocab_size)

    def run(cfg):
        cache = init_cache(cfg, B, cache_len)
        step = jax.jit(lambda p, b, c: decode_step(cfg, p, b, c))
        outs = []
        for i in range(n):
            logits, cache = step(params, {"token": toks[:, i]}, cache)
            outs.append(np.asarray(logits, np.float32))
        return np.stack(outs)

    np.testing.assert_allclose(run(cfg_pal), run(cfg_jnp), atol=3e-3,
                               rtol=1e-3)


# ---------------------------------------------------------------------------
# paged decode kernel vs the jnp gather reference: row lengths on and around
# block edges, unallocated table entries on the trash block, and NaN in every
# pool position no row's length covers (the trash block included)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("H,KV,dh,bs", [
    (32, 2, 128, 16),      # chatglm3-6b-like GQA: 16 query heads a group
    (4, 4, 32, 16),        # MHA (one query head a group)
    (32, 32, 64, 128),     # MHA tile too wide for one step: a group a step
], ids=["gqa", "mha", "mha_wide"])
def test_paged_decode_attention_vs_gather_ref(H, KV, dh, bs):
    from repro.models.attention import _paged_gather, decode_attention
    nb = 4 if bs == 16 else 2
    lens = np.array([0, 1, bs, bs + 1, nb * bs])
    B, NB = len(lens), len(lens) * nb
    rng = np.random.default_rng(0)
    k = rng.standard_normal((NB + 1, KV, bs, dh)).astype(np.float32)
    v = rng.standard_normal((NB + 1, KV, bs, dh)).astype(np.float32)
    table = np.full((B, nb), NB, np.int32)     # NB: the trash block
    free = list(rng.permutation(NB))
    for b, n in enumerate(lens):
        for j in range(-(-n // bs)):
            table[b, j] = free.pop()
            k[table[b, j], :, n - j * bs:] = np.nan
            v[table[b, j], :, n - j * bs:] = np.nan
    k[free + [NB]] = np.nan
    v[free + [NB]] = np.nan
    q = jnp.asarray(rng.standard_normal((B, 1, H, dh)), jnp.float32)
    k, v, table = jnp.asarray(k), jnp.asarray(v), jnp.asarray(table)
    ln = jnp.asarray(lens, jnp.int32)

    out = ops.paged_decode_attention(q, k, v, table, ln, interpret=True)
    gk, gv = _paged_gather(k, table), _paged_gather(v, table)
    vmask = jnp.arange(nb * bs)[None, :, None, None] < ln[:, None, None, None]
    ref = decode_attention(q, gk, jnp.where(vmask, gv, 0), ln, backend="jnp")
    assert not np.isnan(np.asarray(out)).any()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_gla_pallas_forward_dispatch_and_grad():
    """ssm_scan="pallas" forward == jnp chunked scan, and jax.grad through
    the dispatch (kernel fwd + jnp-recompute bwd) == plain jnp grad."""
    from repro.configs import get_reduced
    from repro.kernels.registry import KernelSpec
    from repro.models.ssm import _gla_forward, chunked_gla
    cfg_j = get_reduced("xlstm-350m")
    cfg_p = cfg_j.with_(kernels=KernelSpec(ssm_scan="pallas"))
    B, S, H, dk, dv = 1, 48, 2, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q = _mk(ks[0], (B, S, H, dk), jnp.float32)
    k = _mk(ks[1], (B, S, H, dk), jnp.float32) * 0.3
    v = _mk(ks[2], (B, S, H, dv), jnp.float32)
    g = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))

    y_p = _gla_forward(cfg_p, q, k, v, g, chunk=16)
    y_j = _gla_forward(cfg_j, q, k, v, g, chunk=16)
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_j), atol=5e-5,
                               rtol=5e-5)

    def loss(cfg):
        return lambda q, k, v, g: jnp.sum(
            _gla_forward(cfg, q, k, v, g, chunk=16) ** 2)
    g_p = jax.grad(loss(cfg_p), argnums=(0, 1, 2, 3))(q, k, v, g)
    g_j = jax.grad(loss(cfg_j), argnums=(0, 1, 2, 3))(q, k, v, g)
    for name, gp, gj in zip("qkvg", g_p, g_j):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gj), atol=1e-4,
                                   rtol=1e-4, err_msg=f"d{name}")


# ---------------------------------------------------------------------------
# Block-sparse grid pruning: the Pallas kernels walk flash_grid_plan's tile
# list instead of the dense rectangle. Parity exactly at block boundaries
# (window % bk == 0, window < bk, q_offset != 0) against the dense jnp
# references, plus the plan's own pruning ledger.
# ---------------------------------------------------------------------------

PRUNED_CASES = [
    # B, Sq, Sk, H, KV, dh, causal, window, q_offset, bq, bk
    (2, 128, 128, 4, 2, 32, True, 64, 0, 32, 32),    # window % bk == 0
    (2, 128, 128, 4, 2, 32, True, 16, 0, 32, 32),    # window < bk
    (1, 32, 128, 4, 2, 16, True, 0, 96, 32, 32),     # q_offset != 0
    (1, 32, 128, 4, 2, 16, True, 48, 96, 32, 32),    # offset + window
    (1, 17, 128, 2, 1, 16, True, 0, 50, 16, 32),     # ragged q + offset
    (2, 128, 128, 4, 2, 32, False, 48, 0, 32, 32),   # non-causal window
]


@pytest.mark.parametrize("case", PRUNED_CASES)
def test_flash_pruned_grid_parity_at_block_boundaries(case):
    B, Sq, Sk, H, KV, dh, causal, window, q_offset, bq, bk = case
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q = _mk(ks[0], (B, Sq, H, dh), jnp.float32)
    k = _mk(ks[1], (B, Sk, KV, dh), jnp.float32)
    v = _mk(ks[2], (B, Sk, KV, dh), jnp.float32)
    do = _mk(ks[3], (B, Sq, H, dh), jnp.float32)

    def pallas(q, k, v):
        return ops.flash_attention(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset, bq=bq, bk=bk,
                                   interpret=True)

    def dense(q, k, v):
        return blockwise_attention(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset, block=bk)

    np.testing.assert_allclose(np.asarray(pallas(q, k, v)),
                               np.asarray(dense(q, k, v)),
                               atol=2e-5, rtol=2e-5)

    def scal(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * do)

    g_p = jax.grad(scal(pallas), argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(scal(dense), argnums=(0, 1, 2))(q, k, v)
    for name, gp, gd in zip("qkv", g_p, g_d):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gd), atol=1e-3,
                                   rtol=1e-3, err_msg=f"pruned d{name}")


def test_flash_grid_plan_prunes_masked_tiles():
    from repro.kernels.flash_attention import flash_grid_plan
    # causal square triangle: nq*(nq+1)/2 of nq^2
    plan = flash_grid_plan(512, 512, 64, 64, True, 0, 0, 512)
    assert plan["total"] == 64
    assert plan["visited"] == 8 * 9 // 2
    # sliding window: constant ceil(window/bk)+1 kv blocks per q block
    # (minus the clipped rows at the start of the sequence)
    plan = flash_grid_plan(1024, 1024, 128, 128, True, 256, 0, 1024)
    assert plan["visited"] < plan["total"]
    assert plan["visited"] <= 8 * (256 // 128 + 1)
    # both orders enumerate the same tile set, every block has >= 1 tile
    for a, b in ((plan["qblk"], plan["qblk2"]), (plan["kblk"], plan["kblk2"])):
        assert set(np.asarray(a).tolist()) == set(np.asarray(b).tolist())
    assert set(np.asarray(plan["qblk"]).tolist()) == set(range(8))
    assert set(np.asarray(plan["kblk"]).tolist()) == set(range(8))
    # non-causal dense: nothing pruned
    plan = flash_grid_plan(256, 256, 64, 64, False, 0, 0, 256)
    assert plan["visited"] == plan["total"]
    # windowed prefill chunk (small Sq, long kv prefix): the dkv zeros
    # sentinels for unattended kv blocks must NOT leak into the fwd/dq list
    plan = flash_grid_plan(128, 1024, 128, 128, True, 256, 896, 1024)
    assert plan["visited"] == 3                 # the window band only
    assert plan["visited_dkv"] == 8             # every kv block written


# ---------------------------------------------------------------------------
# Fused GLA backward: gradient parity of the reverse chunk-scan kernel pair
# vs autodiff through the jnp chunked scan, final-state exactness with
# padded tails, and the single-pass property of the traced backward.
# ---------------------------------------------------------------------------

GLA_GRAD_CASES = [
    # B, S, H, dk, dv, chunk, dtype — S not a multiple of chunk covers the
    # zero-padded tail rows; the masked state update keeps them inert.
    (2, 128, 2, 16, 16, 32, jnp.float32),
    (1, 100, 2, 16, 16, 32, jnp.float32),     # padded tail
    (1, 33, 2, 8, 8, 16, jnp.float32),        # mostly-padding last chunk
    (1, 80, 2, 16, 16, 32, jnp.bfloat16),
]


@pytest.mark.parametrize("case", GLA_GRAD_CASES)
def test_gla_fused_backward_parity(case):
    from repro.models.ssm import chunked_gla
    B, S, H, dk, dv, chunk, dt = case
    ks = jax.random.split(jax.random.PRNGKey(13), 5)
    q = _mk(ks[0], (B, S, H, dk), dt)
    k = _mk(ks[1], (B, S, H, dk), dt) * 0.3
    v = _mk(ks[2], (B, S, H, dv), dt)
    g = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    dy = _mk(ks[4], (B, S, H, dv), dt)

    def loss(fn):
        return lambda q, k, v, g: jnp.sum(
            (fn(q, k, v, g) * dy).astype(jnp.float32))

    g_fused = jax.grad(loss(lambda q, k, v, g: ops.gla_scan(
        q, k, v, g, chunk=chunk, interpret=True)),
        argnums=(0, 1, 2, 3))(q, k, v, g)
    g_jnp = jax.grad(loss(lambda q, k, v, g: chunked_gla(
        q, k, v, g, chunk=chunk)[0]), argnums=(0, 1, 2, 3))(q, k, v, g)
    tol = 1e-1 if dt == jnp.bfloat16 else 1e-4
    for name, gf, gj in zip("qkvg", g_fused, g_jnp):
        np.testing.assert_allclose(np.asarray(gf, np.float32),
                                   np.asarray(gj, np.float32), atol=tol,
                                   rtol=tol, err_msg=f"fused d{name}")


def test_gla_final_state_exact_with_padding():
    """ops.gla_scan(return_final_state=True) matches the jnp chunked scan's
    final state when S is not a chunk multiple (regression: padded rows used
    to feed the carried state)."""
    from repro.models.ssm import chunked_gla
    B, S, H, dk, dv, chunk = 2, 77, 2, 8, 8, 32
    ks = jax.random.split(jax.random.PRNGKey(17), 4)
    q = _mk(ks[0], (B, S, H, dk), jnp.float32)
    k = _mk(ks[1], (B, S, H, dk), jnp.float32) * 0.3
    v = _mk(ks[2], (B, S, H, dv), jnp.float32)
    g = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    y, fin = ops.gla_scan(q, k, v, g, chunk=chunk, interpret=True,
                          return_final_state=True)
    y_ref, st_ref = chunked_gla(q, k, v, g, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=5e-5,
                               rtol=5e-5)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(st_ref),
                               atol=5e-5, rtol=5e-5)


def test_gla_state_update_masks_padded_rows():
    """Direct kernel call with a GARBAGE padded tail (g > 0, nonzero k/v):
    s_valid must keep the tail out of the carried state entirely."""
    from repro.kernels.ssm_scan import gla_scan_kernel
    BH, S, Spad, dk, dv, chunk = 2, 33, 48, 8, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(19), 4)
    q = _mk(ks[0], (BH, Spad, dk), jnp.float32)
    k = _mk(ks[1], (BH, Spad, dk), jnp.float32) * 0.3
    v = _mk(ks[2], (BH, Spad, dv), jnp.float32)
    g = -jax.nn.softplus(jax.random.normal(ks[3], (BH, Spad)))
    g = g.at[:, S:].set(0.7)              # decay > 1 garbage in the pad
    y, fin = gla_scan_kernel(q, k, v, g, chunk=chunk, s_valid=S,
                             interpret=True)
    ref_state = ref.gla_final_state_ref(q[:, :S], k[:, :S], v[:, :S],
                                        g[:, :S])
    np.testing.assert_allclose(np.asarray(fin), np.asarray(ref_state),
                               atol=5e-5, rtol=5e-5)
    r = ref.gla_scan_ref(q[:, :S], k[:, :S], v[:, :S], g[:, :S])
    np.testing.assert_allclose(np.asarray(y[:, :S]), np.asarray(r),
                               atol=5e-5, rtol=5e-5)


def test_gla_pallas_backward_is_single_pass():
    """The traced backward of the pallas ssm_scan path is the fused kernel
    pair: exactly two pallas_calls (fwd + reverse scan) and NO lax.scan
    recompute through the jnp chunked scan."""
    import re
    B, S, H, dk, dv = 1, 64, 2, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(23), 4)
    q = _mk(ks[0], (B, S, H, dk), jnp.float32)
    k = _mk(ks[1], (B, S, H, dk), jnp.float32) * 0.3
    v = _mk(ks[2], (B, S, H, dv), jnp.float32)
    g = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))

    def loss(q, k, v, g):
        return jnp.sum(ops.gla_scan(q, k, v, g, chunk=16, interpret=True)
                       ** 2)

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3)))(
        q, k, v, g))
    assert text.count("pallas_call") == 2, text.count("pallas_call")
    assert not re.search(r"\bscan\[", text)


GLA_CASES = [
    (2, 128, 2, 16, 32, 32, jnp.float32),
    (1, 100, 4, 8, 8, 16, jnp.float32),
    (1, 64, 2, 32, 64, 64, jnp.bfloat16),
]


@pytest.mark.parametrize("case", GLA_CASES)
def test_gla_scan_vs_ref(case):
    B, S, H, dk, dv, chunk, dt = case
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = _mk(ks[0], (B, S, H, dk), dt)
    k = _mk(ks[1], (B, S, H, dk), dt) * 0.3
    v = _mk(ks[2], (B, S, H, dv), dt)
    g = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    y = ops.gla_scan(q, k, v, g.astype(dt) if dt != jnp.float32 else g,
                     chunk=chunk, interpret=True)

    def fold(x):
        return jnp.moveaxis(x, 2, 1).reshape((B * H,) + x.shape[1:2] + x.shape[3:])
    r = ref.gla_scan_ref(fold(q), fold(k), fold(v),
                         jnp.moveaxis(g, 2, 1).reshape(B * H, S))
    r = jnp.moveaxis(r.reshape(B, H, S, dv), 1, 2)
    tol = 5e-2 if dt == jnp.bfloat16 else 5e-5
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(r, np.float32), atol=tol, rtol=tol)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3), st.sampled_from([32, 48, 64]),
       st.sampled_from([1, 2, 4]), st.booleans())
def test_flash_property_random_shapes(b, s, kv, causal):
    h = kv * 2
    dh = 16
    ks = jax.random.split(jax.random.PRNGKey(s + b), 3)
    q = _mk(ks[0], (b, s, h, dh), jnp.float32)
    k = _mk(ks[1], (b, s, kv, dh), jnp.float32)
    v = _mk(ks[2], (b, s, kv, dh), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=causal, bq=16, bk=16,
                              interpret=True)
    qh = jnp.moveaxis(q, 2, 1).reshape(b * h, s, dh)
    kh = jnp.moveaxis(k, 2, 1).reshape(b * kv, s, dh)
    vh = jnp.moveaxis(v, 2, 1).reshape(b * kv, s, dh)
    r = ref.flash_attention_ref(qh, kh, vh, causal=causal, group=h // kv)
    r = jnp.moveaxis(r.reshape(b, h, s, dh), 1, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r), atol=2e-5,
                               rtol=2e-5)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 2), st.sampled_from([33, 64, 80]),
       st.sampled_from([8, 16]))
def test_gla_property_random_shapes(b, s, chunk):
    h, dk, dv = 2, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(s), 4)
    q = _mk(ks[0], (b, s, h, dk), jnp.float32)
    k = _mk(ks[1], (b, s, h, dk), jnp.float32) * 0.3
    v = _mk(ks[2], (b, s, h, dv), jnp.float32)
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, s, h)))
    y = ops.gla_scan(q, k, v, g, chunk=chunk, interpret=True)

    def fold(x):
        return jnp.moveaxis(x, 2, 1).reshape((b * h, s) + x.shape[3:])
    r = ref.gla_scan_ref(fold(q), fold(k), fold(v),
                         jnp.moveaxis(g, 2, 1).reshape(b * h, s))
    r = jnp.moveaxis(r.reshape(b, h, s, dv), 1, 2)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r), atol=5e-5,
                               rtol=5e-5)
