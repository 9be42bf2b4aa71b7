"""Copy-on-write prefix sharing + self-speculative decode (ISSUE 8).

Pins the tentpole invariants:
  * prefix-shared admissions emit byte-identical token streams to the
    unshared engine, across fp-paged / int8 / svd pools, including when
    the divergence point falls mid-page (the copy-on-write split path);
  * page refcounts conserve under admission/retirement/eviction churn —
    the allocator's free list always equals its zero-ref pages, and
    evicting every retired prefix returns the pool to fully free;
  * cow_split_pages copies exactly the shared window of the divergent
    page (and nothing else) on device;
  * speculative decode (accept AND reject paths) reproduces the
    sequential greedy stream, and a full-prompt replay drafts from the
    retired donor stream at ~100% acceptance;
  * the Lq-folded paged decode kernel matches the dense reference for
    multi-row (verify-shaped) queries.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import RunConfig, get_config
from repro.models import init_model
from repro.serve import Request, SamplingParams, ServeEngine

RCFG = RunConfig(compute_dtype="float32", param_dtype="float32",
                 policy_name="none")

POOL_VARIANTS = {
    "fp": dict(cache_layout="paged", page_size=8),
    "int8": dict(cache_layout="paged", page_size=8, cache_compress="int8"),
    "svd": dict(cache_layout="paged", page_size=8,
                cache_compress="svd(r=1/2)"),
}


def _setup():
    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    return cfg, params


def _shared_prefix_requests(cfg, n=4, prefix_len=20, max_new=6, seed=0):
    """n prompts sharing a prefix, with per-request tails of growing
    length so divergence points land both mid-page and page-aligned."""
    rng = np.random.default_rng(seed)
    head = rng.integers(1, cfg.vocab_size, size=prefix_len).tolist()
    return [Request(uid=i,
                    tokens=head + rng.integers(
                        1, cfg.vocab_size, size=3 + i).tolist(),
                    max_new_tokens=max_new)
            for i in range(n)]


def _evict_all_retired(eng):
    while eng._evict_one_retired():
        pass


def _fully_free(eng):
    for alloc in eng.allocators:
        alloc.check_invariant()
        assert alloc.free_pages == alloc.spec.n_pages, "pages leaked"


# ---------------------------------------------------------------------------
# copy-on-write prefix sharing
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("variant", sorted(POOL_VARIANTS))
def test_cow_shared_prefix_matches_unshared(variant):
    """Shared-prefix batch == unshared engine == solo runs, per pool
    format, and the sharing actually happened (hits + cow splits)."""
    cfg, params = _setup()
    kw = dict(max_slots=4, max_len=64, decode_block=3,
              **POOL_VARIANTS[variant])
    reqs = lambda: _shared_prefix_requests(cfg)

    base = ServeEngine(cfg, RCFG, params, **kw).run(reqs())
    eng = ServeEngine(cfg, RCFG, params, prefix_share=True, **kw)
    out = eng.run(reqs())
    for i in base:
        assert out[i].tokens == base[i].tokens, f"request {i} diverged"
        solo = ServeEngine(cfg, RCFG, params, prefix_share=True,
                           **{**kw, "max_slots": 1})
        assert solo.run([reqs()[i]])[i].tokens == base[i].tokens
    st = eng.stats()
    assert st["prefix_hits"] >= 3
    assert st["prefix_pages_adopted"] > 0
    assert st["cow_page_splits"] > 0          # 20-token head, 8-token pages
    _evict_all_retired(eng)
    _fully_free(eng)


def test_cow_divergence_points_cover_page_boundary_cases():
    """Divergence exactly ON a page boundary (no split needed) and one
    token past it (split of a 1-token window) both stay bit-identical."""
    cfg, params = _setup()
    rng = np.random.default_rng(3)
    head = rng.integers(1, cfg.vocab_size, size=16).tolist()   # 2 full pages
    prompts = [
        head + rng.integers(1, cfg.vocab_size, size=5).tolist(),  # owner
        head + rng.integers(1, cfg.vocab_size, size=4).tolist(),  # diverge @16
    ]
    # share exactly 17 tokens with prompt 0: 1-token window mid-page split
    prompts.append(prompts[0][:17]
                   + [(prompts[0][17] + 1) % cfg.vocab_size, 5])
    mk = lambda: [Request(uid=i, tokens=p, max_new_tokens=5)
                  for i, p in enumerate(prompts)]
    kw = dict(max_slots=3, max_len=48, decode_block=2, cache_layout="paged",
              page_size=8)
    base = ServeEngine(cfg, RCFG, params, **kw).run(mk())
    eng = ServeEngine(cfg, RCFG, params, prefix_share=True, **kw)
    out = eng.run(mk())
    for i in base:
        assert out[i].tokens == base[i].tokens, f"request {i} diverged"
    assert eng.stats()["prefix_hits"] >= 2
    _evict_all_retired(eng)
    _fully_free(eng)


def test_cow_refcount_invariant_under_eviction_churn():
    """Waves of shared-prefix traffic through a pool too small to keep
    every retired prefix: retired entries get evicted under pressure,
    refcounts conserve at every step, and tokens never change."""
    cfg, params = _setup()
    rng = np.random.default_rng(4)
    heads = [rng.integers(1, cfg.vocab_size, size=16).tolist()
             for _ in range(3)]

    def wave(w):
        return [Request(uid=100 * w + i,
                        tokens=heads[(w + i) % 3] + rng.integers(
                            1, cfg.vocab_size, size=3 + i).tolist(),
                        max_new_tokens=4)
                for i in range(3)]

    waves = [wave(w) for w in range(4)]
    kw = dict(max_slots=2, max_len=48, decode_block=2, cache_layout="paged",
              page_size=8, pool_tokens=96)   # 12 pages: forces eviction
    base = {}
    for w in waves:
        base.update(ServeEngine(cfg, RCFG, params, **kw).run(
            [Request(uid=r.uid, tokens=r.tokens,
                     max_new_tokens=r.max_new_tokens) for r in w]))
    eng = ServeEngine(cfg, RCFG, params, prefix_share=True, prefix_cache=2,
                      **kw)
    for w in waves:
        for r in w:
            eng.submit(r)
        while eng.has_work:
            for out in eng.step():
                assert out.tokens == base[out.uid].tokens, \
                    f"request {out.uid} diverged"
            for alloc in eng.allocators:
                alloc.check_invariant()
    assert eng.stats()["prefix_hits"] > 0
    _evict_all_retired(eng)
    _fully_free(eng)


def test_cow_split_pages_copies_exact_window():
    """Device-level unit test: cow_split_pages moves only the rows of
    the source page whose positions fall in [lo, hi), preserving their
    page_pos, and leaves every other page untouched."""
    from repro.models.attention import PagedKVCache
    from repro.serve.cache import cow_split_pages

    layers, n_pages, ps, KV, dh = 2, 6, 8, 2, 16
    rng = np.random.default_rng(5)
    kp = rng.standard_normal((layers, n_pages, ps, KV, dh)).astype(np.float32)
    pp = np.full((layers, n_pages, ps), -1, np.int32)
    pp[:, 2] = np.arange(16, 16 + ps)         # src page holds tokens 16..23
    node = PagedKVCache(k_pages=jnp.asarray(kp), v_pages=jnp.asarray(kp),
                        page_pos=jnp.asarray(pp),
                        block_table=jnp.full((layers, 1, 4), -1, jnp.int32),
                        ring=jnp.zeros((layers,), jnp.int32))
    out = cow_split_pages(node, jnp.int32(2), jnp.int32(4),
                          jnp.int32(16), jnp.int32(20))
    got_pp = np.asarray(out.page_pos)
    np.testing.assert_array_equal(got_pp[:, 4, :4], pp[:, 2, :4])
    assert (got_pp[:, 4, 4:] == -1).all()      # outside [lo, hi): untouched
    np.testing.assert_array_equal(np.asarray(out.k_pages)[:, 4, :4],
                                  kp[:, 2, :4])
    np.testing.assert_array_equal(np.asarray(out.k_pages)[:, 2], kp[:, 2])
    # -1 sentinels are a no-op
    noop = cow_split_pages(node, jnp.int32(-1), jnp.int32(4),
                           jnp.int32(16), jnp.int32(20))
    np.testing.assert_array_equal(np.asarray(noop.page_pos),
                                  np.asarray(node.page_pos))


def test_cow_capacity_multiplier_at_fixed_pool():
    """16 requests sharing a long prompt at a pool that fits ~2 unshared
    reservations: prefix sharing must raise admissible concurrency by at
    least 2x while every stream matches the unshared engine."""
    cfg, params = _setup()
    rng = np.random.default_rng(6)
    head = rng.integers(1, cfg.vocab_size, size=48).tolist()
    prompts = [head + rng.integers(1, cfg.vocab_size,
                                   size=1 + i % 3).tolist()
               for i in range(8)]
    mk = lambda: [Request(uid=i, tokens=prompts[i], max_new_tokens=4)
                  for i in range(8)]
    kw = dict(max_slots=8, max_len=64, decode_block=2, cache_layout="paged",
              page_size=8, pool_tokens=168)   # 21 pages; ~7/request unshared
    base = ServeEngine(cfg, RCFG, params, **kw)
    out_b = base.run(mk())
    eng = ServeEngine(cfg, RCFG, params, prefix_share=True, **kw)
    out_s = eng.run(mk())
    for i in out_b:
        assert out_s[i].tokens == out_b[i].tokens, f"request {i} diverged"
    assert eng.peak_active >= 2 * base.peak_active
    _evict_all_retired(eng)
    _fully_free(eng)


def test_prefix_share_gating_raises():
    cfg, params = _setup()
    with pytest.raises(ValueError, match="cache_layout='paged'"):
        ServeEngine(cfg, RCFG, params, max_slots=2, max_len=32,
                    prefix_share=True)
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(cfg, RCFG, params, max_slots=2, max_len=32,
                    speculative_k=2)


# ---------------------------------------------------------------------------
# speculative decode
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("variant", ["fp", "int8"])
def test_speculative_stream_matches_sequential_greedy(variant):
    """k=4 speculative decode (n-gram drafts: mostly rejects, sometimes
    accepts) emits the exact sequential greedy stream per request."""
    cfg, params = _setup()
    kw = dict(max_slots=3, max_len=64, decode_block=3,
              **POOL_VARIANTS[variant])
    rng = np.random.default_rng(7)
    mk = lambda: [Request(uid=i, tokens=rng.integers(
                      1, cfg.vocab_size, size=8 + 3 * i).tolist(),
                      max_new_tokens=10) for i in range(3)]
    reqs = mk()
    base = ServeEngine(cfg, RCFG, params, **kw).run(
        [Request(uid=r.uid, tokens=r.tokens,
                 max_new_tokens=r.max_new_tokens) for r in reqs])
    eng = ServeEngine(cfg, RCFG, params, speculative_k=4, **kw)
    out = eng.run(reqs)
    for i in base:
        assert out[i].tokens == base[i].tokens, f"request {i} diverged"
    st = eng.stats()
    assert st["spec_verify_calls"] > 0
    assert st["spec_tokens_drafted"] > 0


def test_speculative_replay_accepts_from_donor():
    """A full-prompt replay drafts from the retired donor's stream: the
    replay phase must accept ~every draft and still match the baseline."""
    cfg, params = _setup()
    kw = dict(max_slots=4, max_len=64, decode_block=3, cache_layout="paged",
              page_size=8)
    rng = np.random.default_rng(8)
    prompts = [rng.integers(1, cfg.vocab_size, size=10 + i).tolist()
               for i in range(4)]
    mk = lambda off: [Request(uid=off + i, tokens=prompts[i],
                              max_new_tokens=8) for i in range(4)]
    base = ServeEngine(cfg, RCFG, params, **kw).run(mk(0))
    eng = ServeEngine(cfg, RCFG, params, prefix_share=True, speculative_k=4,
                      **kw)
    r1 = eng.run(mk(0))
    d0, a0 = eng.spec_tokens_drafted, eng.spec_tokens_accepted
    r2 = eng.run(mk(100))
    for i in range(4):
        assert r1[i].tokens == base[i].tokens
        assert r2[100 + i].tokens == base[i].tokens, f"replay {i} diverged"
    # the last verify block drafts past the donor stream's end and pads
    # with n-gram guesses, so ~100% means "well above the cold phase",
    # not literally every draft
    cold_rate = a0 / max(1, d0)
    replay_rate = ((eng.spec_tokens_accepted - a0)
                   / max(1, eng.spec_tokens_drafted - d0))
    assert replay_rate > 0.7, f"donor drafting broke: {replay_rate:.2f}"
    assert replay_rate > cold_rate, (replay_rate, cold_rate)


def test_speculative_falls_back_when_batch_samples():
    """A sampling (temperature > 0) request in the batch drops the block
    to the sequential loop — streams must still match the non-spec
    engine for every request."""
    cfg, params = _setup()
    kw = dict(max_slots=2, max_len=48, decode_block=3, cache_layout="paged",
              page_size=8)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, cfg.vocab_size, size=7 + i).tolist()
               for i in range(2)]
    mk = lambda: [Request(uid=i, tokens=prompts[i], max_new_tokens=6,
                          sampling=SamplingParams(
                              temperature=0.8 if i == 1 else 0.0,
                              top_k=8 if i == 1 else 0, seed=11 + i))
                  for i in range(2)]
    reqs = mk()
    base = ServeEngine(cfg, RCFG, params, **kw).run(mk())
    eng = ServeEngine(cfg, RCFG, params, speculative_k=4, **kw)
    out = eng.run(reqs)
    for i in base:
        assert out[i].tokens == base[i].tokens, f"request {i} diverged"
    assert eng.stats()["spec_verify_calls"] == 0   # sampler present all along


# ---------------------------------------------------------------------------
# multi-row (verify-shaped) kernel parity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,H,KV,dh,ps,Lq", [
    (2, 64, 4, 2, 64, 16, 4),      # GQA, k=3 verify shape
    (1, 96, 4, 1, 32, 8, 5),       # MQA
    (2, 32, 8, 2, 80, 8, 2),       # non-128 head dim
    (2, 256, 16, 8, 128, 128, 4),  # the serve cells' widths and pages
])
def test_flash_paged_decode_multirow_vs_dense_ref(B, S, H, KV, dh, ps, Lq):
    """The Lq-folded paged kernel == dense reference for the short-Lq
    verify shape speculative decode runs through."""
    from repro.kernels.flash_decode import (flash_decode_ref,
                                            flash_paged_decode_kernel)
    from tests.test_paging import _random_paging

    rng = np.random.default_rng(10)
    k = rng.standard_normal((B, S, KV, dh)).astype(np.float32)
    v = rng.standard_normal((B, S, KV, dh)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((B, Lq, H, dh)), jnp.float32)
    n_valid = np.array([S - 3] + [S // 2] * (B - 1))[:B]
    spos = np.where(np.arange(S)[None] < n_valid[:, None],
                    np.arange(S)[None], -1).astype(np.int32)
    # verify rows sit at consecutive positions ending at the write front
    qpos = (n_valid[:, None] - Lq + np.arange(Lq)[None]).astype(np.int32)
    kp, vp, ppos, bt = _random_paging(k, v, spos, ps,
                                      n_pages=2 + B * (S // ps))
    o_ref = flash_decode_ref(q, jnp.asarray(k), jnp.asarray(v),
                             jnp.asarray(qpos), jnp.asarray(spos),
                             causal=True, window=0)
    o_kern = flash_paged_decode_kernel(q, jnp.asarray(kp), jnp.asarray(vp),
                                       jnp.asarray(qpos), jnp.asarray(bt),
                                       jnp.asarray(ppos), causal=True,
                                       window=0, interpret=True)
    np.testing.assert_allclose(np.asarray(o_kern), np.asarray(o_ref),
                               atol=2e-5)
