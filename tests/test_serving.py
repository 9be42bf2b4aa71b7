"""Serving engine + decode-kernel tests (ISSUE 2).

Covers: the flash_decode kernel against its oracle and the training sdpa
math; the flash_attention bq != bk padding regression; sampling semantics;
engine prefill+decode equivalence against ``attn_train`` math for GQA /
MQA / sliding-window / vision cross-attention archs; and the continuous-
batching scheduler invariant — tokens identical to single-request runs
while requests of different lengths join and leave mid-stream.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import RunConfig, get_config
from repro.core.policies import ExactPolicy
from repro.models import forward, init_model
from repro.serve import Request, SamplingParams, ServeEngine, sample_tokens

RCFG = RunConfig(compute_dtype="float32", param_dtype="float32",
                 policy_name="none")


def _make_prompts(cfg, n, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=l).tolist()
            for l in lengths[:n]]


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,H,KV,dh,window,n_valid", [
    (2, 64, 4, 2, 64, 0, 64),      # GQA
    (1, 96, 4, 1, 32, 0, 50),      # MQA, partially filled cache
    (2, 37, 8, 2, 80, 0, 37),      # non-divisible S, non-128 head dim
    (1, 16, 2, 2, 128, 8, 16),     # ring cache: S == window
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_kernel_vs_ref(B, S, H, KV, dh, window, n_valid, dtype):
    from repro.kernels.flash_decode import flash_decode_kernel, flash_decode_ref

    q = jax.random.normal(jax.random.key(0), (B, 1, H, dh), dtype)
    k = jax.random.normal(jax.random.key(1), (B, S, KV, dh), dtype)
    v = jax.random.normal(jax.random.key(2), (B, S, KV, dh), dtype)
    spos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    spos = jnp.where(spos < n_valid, spos, -1)
    qpos = jnp.full((B,), n_valid - 1, jnp.int32)
    o_k = flash_decode_kernel(q, k, v, qpos, spos, causal=True, window=window,
                              bk=16, interpret=True)
    o_r = flash_decode_ref(q, k, v, qpos, spos, causal=True, window=window)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32), atol=tol)


def test_flash_decode_matches_sdpa_chunk1():
    """The decode path reproduces the old chunk=1 sdpa math exactly."""
    from repro.kernels.flash_decode import flash_decode_ref
    from repro.models.attention import sdpa

    B, S, H, KV, dh = 2, 24, 4, 2, 64
    q = jax.random.normal(jax.random.key(3), (B, 1, H, dh))
    k = jax.random.normal(jax.random.key(4), (B, S, KV, dh))
    v = jax.random.normal(jax.random.key(5), (B, S, KV, dh))
    spos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    qpos2d = jnp.full((B, 1), S - 1, jnp.int32)
    o_old = sdpa(q, k, v, qpos2d, spos, causal=True, window=0, chunk=1)
    o_new = flash_decode_ref(q, k, v, qpos2d[:, 0], spos, causal=True, window=0)
    np.testing.assert_allclose(np.asarray(o_old), np.asarray(o_new), atol=1e-5)


@pytest.mark.parametrize("L,bq,bk", [(96, 64, 32), (80, 32, 64), (100, 64, 64)])
def test_flash_attention_bq_ne_bk_regression(L, bq, bk):
    """Padding bug: kv length must pad to a multiple of bk, not bq —
    mismatched block sizes used to mis-size the kv grid and drop tail keys."""
    from repro.kernels import ops, ref

    B, H, KV, dh = 2, 4, 2, 64
    q = jax.random.normal(jax.random.key(6), (B, L, H, dh))
    k = jax.random.normal(jax.random.key(7), (B, L, KV, dh))
    v = jax.random.normal(jax.random.key(8), (B, L, KV, dh))
    o = ops.flash_attention(q, k, v, causal=True, bq=bq, bk=bk)
    o_r = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_r), atol=2e-5)


# ---------------------------------------------------------------------------
# data pipeline bugfix
# ---------------------------------------------------------------------------
def test_pipeline_shard_divisibility_message():
    from repro.data import SyntheticStream

    with pytest.raises(ValueError, match="num_shards must divide global_batch"):
        SyntheticStream(vocab_size=64, seq_len=8, global_batch=5, num_shards=2)


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------
def test_sampling_greedy_is_argmax():
    logits = jax.random.normal(jax.random.key(9), (4, 33))
    zero = jnp.zeros(4, jnp.int32)
    toks = sample_tokens(logits, jnp.arange(4, dtype=jnp.int32), zero,
                         jnp.zeros((4,)), zero)
    np.testing.assert_array_equal(np.asarray(toks),
                                  np.asarray(jnp.argmax(logits, -1)))


def test_sampling_top_k_support():
    logits = jax.random.normal(jax.random.key(10), (64, 50))
    zero = jnp.zeros(64, jnp.int32)
    k = 5
    toks = sample_tokens(logits, jnp.arange(64, dtype=jnp.int32), zero,
                         jnp.full((64,), 1.5), jnp.full((64,), k, jnp.int32))
    order = np.argsort(-np.asarray(logits), axis=-1)
    for b in range(64):
        assert int(toks[b]) in order[b, :k]


def test_sampling_top_k_tied_logits_keep_exactly_k_lowest_indices():
    """Regression: when logits tie at the k-th value, the support must
    stay exactly k wide with the lower token indices winning (stable
    argsort ranks). The old threshold test (scaled >= kth value) kept
    every tied token, silently widening the support."""
    V, k, draws = 12, 3, 64
    logits = jnp.zeros((draws, V))            # all V logits tie
    toks = sample_tokens(logits, jnp.arange(draws, dtype=jnp.int32),
                         jnp.zeros(draws, jnp.int32), jnp.full((draws,), 1.0),
                         jnp.full((draws,), k, jnp.int32))
    assert set(np.asarray(toks).tolist()) <= set(range(k))
    # tie straddling the boundary: [5, 5, 1, 5, ...] with k=2 keeps
    # tokens {0, 1} (indices of the first two 5s), never token 3
    row = jnp.zeros((draws, V)).at[:, [0, 1, 3]].set(5.0).at[:, 2].set(1.0)
    toks = sample_tokens(row, jnp.arange(draws, dtype=jnp.int32),
                         jnp.zeros(draws, jnp.int32), jnp.full((draws,), 1.0),
                         jnp.full((draws,), 2, jnp.int32))
    assert set(np.asarray(toks).tolist()) <= {0, 1}


def test_percentile_nearest_rank():
    """Regression: p50 of two samples is the FIRST (nearest-rank
    ceil(p*n)), not the max — int(p*n) indexing overshot."""
    from repro.serve.engine import _percentile

    assert _percentile([1.0, 9.0], 0.50) == 1.0
    assert _percentile([1.0, 9.0], 0.95) == 9.0
    assert _percentile([1.0, 2.0, 3.0], 0.50) == 2.0
    assert _percentile([7.0], 0.50) == 7.0 and _percentile([7.0], 0.95) == 7.0
    assert _percentile([], 0.50) == 0.0
    samples = [float(i) for i in range(1, 21)]
    assert _percentile(samples, 0.95) == 19.0   # ceil(.95*20)=19th sample
    assert _percentile(samples, 1.00) == 20.0


def test_sampling_deterministic_per_seed_and_index():
    """The stream depends only on (seed, token index) — not slot or step.
    Both rows carry the same logits, so equal (seed, index) pairs must draw
    the same token whichever row they sit in."""
    logits = jnp.tile(jax.random.normal(jax.random.key(11), (1, 40)), (2, 1))
    t = jnp.full((2,), 0.9)
    k0 = jnp.zeros((2,), jnp.int32)
    a = sample_tokens(logits, jnp.array([7, 7]), jnp.array([3, 3]), t, k0)
    assert int(a[0]) == int(a[1])
    b = sample_tokens(logits, jnp.array([7, 8]), jnp.array([3, 3]), t, k0)
    c = sample_tokens(logits, jnp.array([7, 7]), jnp.array([3, 4]), t, k0)
    # different seed or token index may move the draw; same pair never does
    assert int(a[0]) == int(b[0]) == int(c[0])


# ---------------------------------------------------------------------------
# engine: prefill+decode equivalence vs attn_train (forward) math
# ---------------------------------------------------------------------------
EQUIV_ARCHS = [
    "internlm2-1.8b_smoke",            # GQA 4/2
    "mqa",                             # MQA (kv=1) variant
    "h2o-danube-3-4b_smoke",           # sliding-window ring cache
    "llama-3.2-vision-11b_smoke",      # vision cross-attention
    "qwen3-32b_smoke",                 # qk-norm
]


def _cfg_for(name):
    if name == "mqa":
        base = get_config("internlm2-1.8b_smoke")
        return dataclasses.replace(base, name="mqa_smoke", n_kv_heads=1)
    return get_config(name)


@pytest.mark.parametrize("arch", EQUIV_ARCHS)
def test_engine_greedy_matches_full_forward(arch):
    """Engine tokens == argmax of teacher-forced attn_train logits.

    Generation length pushes past danube's window (8) so the ring cache's
    wrap-around is exercised against the train path's window mask.
    """
    cfg = _cfg_for(arch)
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    B, lp, gen = 2, 12, 10
    prompts = _make_prompts(cfg, B, [lp, lp - 4])
    rng = np.random.default_rng(1)
    imgs = (rng.standard_normal((B, cfg.vision_tokens, cfg.d_model),
                                dtype=np.float32)
            if cfg.vision_tokens else [None] * 2)

    eng = ServeEngine(cfg, RCFG, params, max_slots=B, max_len=48, decode_block=4)
    res = eng.run([
        Request(uid=i, tokens=prompts[i], max_new_tokens=gen,
                image_embeds=imgs[i] if cfg.vision_tokens else None)
        for i in range(B)
    ])

    for i in range(B):
        toks = res[i].tokens
        assert len(toks) == gen
        seq = prompts[i] + toks
        batch = {"tokens": jnp.asarray(seq, jnp.int32)[None],
                 "labels": jnp.zeros((1, len(seq)), jnp.int32)}
        if cfg.vision_tokens:
            batch["image_embeds"] = jnp.asarray(imgs[i])[None]
        h, _ = forward(cfg, RCFG, ExactPolicy(), params, batch, jax.random.key(2))
        logits = (h[0] @ params["head"]).astype(jnp.float32)[:, : cfg.vocab_size]
        want = np.asarray(jnp.argmax(logits, -1))
        lp_i = len(prompts[i])
        np.testing.assert_array_equal(np.asarray(toks), want[lp_i - 1 : lp_i - 1 + gen])


# ---------------------------------------------------------------------------
# engine: continuous-batching scheduler
# ---------------------------------------------------------------------------
def test_scheduler_join_leave_matches_single_runs():
    """4 requests of different prompt/generation lengths through 2 slots:
    admissions and evictions interleave mid-stream, and every token stream
    is identical to the same request run alone."""
    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    prompts = _make_prompts(cfg, 4, [8, 11, 6, 14])
    reqs = [
        Request(uid=i, tokens=prompts[i], max_new_tokens=4 + 3 * i,
                sampling=SamplingParams(
                    temperature=0.8 if i % 2 else 0.0,
                    top_k=8 if i % 2 else 0, seed=100 + i))
        for i in range(4)
    ]
    eng = ServeEngine(cfg, RCFG, params, max_slots=2, max_len=64, decode_block=3)
    batched = eng.run(reqs)
    assert sorted(batched) == [0, 1, 2, 3]
    for i, req in enumerate(reqs):
        assert len(batched[i].tokens) == req.max_new_tokens
        solo_eng = ServeEngine(cfg, RCFG, params, max_slots=1, max_len=64,
                               decode_block=3)
        solo = solo_eng.run([req])[i]
        assert solo.tokens == batched[i].tokens, f"request {i} diverged"


def test_scheduler_eos_frees_slot_for_queue():
    """An eos stop mid-block evicts the request; a queued request takes the
    slot and still matches its solo run."""
    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    prompts = _make_prompts(cfg, 3, [8, 9, 10], seed=3)

    probe = ServeEngine(cfg, RCFG, params, max_slots=1, max_len=64, decode_block=4)
    free_run = probe.run([Request(uid=0, tokens=prompts[0], max_new_tokens=8)])[0]
    eos = free_run.tokens[2]  # force an eos hit on the 3rd generated token

    reqs = [Request(uid=0, tokens=prompts[0], max_new_tokens=8, eos_id=eos),
            Request(uid=1, tokens=prompts[1], max_new_tokens=6),
            Request(uid=2, tokens=prompts[2], max_new_tokens=5)]
    eng = ServeEngine(cfg, RCFG, params, max_slots=1, max_len=64, decode_block=4)
    out = eng.run(reqs)
    assert out[0].finish_reason == "eos"
    assert out[0].tokens == free_run.tokens[:3]
    for i in (1, 2):
        solo = ServeEngine(cfg, RCFG, params, max_slots=1, max_len=64,
                           decode_block=4).run([reqs[i]])[i]
        assert out[i].tokens == solo.tokens


def test_greedy_decode_fused_equals_per_token():
    """train.serve_step: the engine-backed greedy_decode reproduces the
    legacy per-token loop token for token."""
    from repro.data import SyntheticStream
    from repro.train.serve_step import greedy_decode, greedy_decode_per_token

    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    stream = SyntheticStream.for_arch(cfg, 16, 2)
    batch = {k: jnp.asarray(v) for k, v in stream.get_batch(0).items()
             if k in ("tokens",)}
    fused = greedy_decode(cfg, RCFG, params, batch, steps=8, max_len=32)
    loop = greedy_decode_per_token(cfg, RCFG, params, batch, steps=8, max_len=32)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(loop))


# ---------------------------------------------------------------------------
# serving config / plan plumbing
# ---------------------------------------------------------------------------
def test_prefill_with_compression_plan_is_exact():
    """A serving CompressionPlan resolves + dispatches but never changes
    logits (forward math is exact for every policy)."""
    from repro.models import prefill

    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    batch = {"tokens": jnp.asarray(_make_prompts(cfg, 1, [12])[0], jnp.int32)[None]}
    l0, _ = prefill(cfg, RCFG, params, batch, 32)
    l1, _ = prefill(cfg, RCFG, params, batch, 32,
                    plan="attn.qkv=pamm(r=1/8,eps=inf);ffn.*=compact(r=1/4)")
    np.testing.assert_allclose(np.asarray(l0), np.asarray(l1), atol=1e-6)


def test_serve_cli_smoke_dtype_compression(capsys):
    from repro.launch.serve import main

    main(["--arch", "internlm2-1.8b_smoke", "--batch", "2", "--requests", "2",
          "--prompt-len", "10", "--gen", "4", "--dtype", "bfloat16",
          "--compression", "attn.qkv=pamm(r=1/8)", "--smoke"])
    out = capsys.readouterr().out
    assert "SMOKE OK" in out


def test_prefill_through_pallas_kernel_matches_jnp():
    """rcfg.attn_kernel='pallas' routes prefill attention through the
    FlashAttention kernel (interpret mode off-TPU) with identical logits."""
    from repro.models import prefill

    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    batch = {"tokens": jnp.asarray(_make_prompts(cfg, 1, [16])[0], jnp.int32)[None]}
    l_jnp, c_jnp = prefill(cfg, RCFG, params, batch, 32)
    rk = dataclasses.replace(RCFG, attn_kernel="pallas")
    l_pal, c_pal = prefill(cfg, rk, params, batch, 32)
    np.testing.assert_allclose(np.asarray(l_jnp), np.asarray(l_pal), atol=2e-4)
    for a, b in zip(jax.tree.leaves(c_jnp), jax.tree.leaves(c_pal)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)
