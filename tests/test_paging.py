"""Paged KV-cache runtime tests (ISSUE 5).

Covers: the paged flash-decode kernel and jnp oracle against the dense
oracle (pages scattered randomly through a block table); the host-side
PageAllocator (alloc/free/append, exhaustion, the free-xor-owned
invariant); token-identical paged-vs-dense engine parity across GQA /
MQA / sliding-window-ring / vision / qk-norm archs; a churn run through
a constrained pool proving freed pages are reused and never leak;
page-gated admission; prompt-length bucketing (one prefill compile per
bucket, pad rows kept out of the spliced cache); and the reserved-vs-used
telemetry.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import RunConfig, get_config
from repro.models import init_model
from repro.serve import PageAllocator, PoolSpec, Request, SamplingParams, ServeEngine

RCFG = RunConfig(compute_dtype="float32", param_dtype="float32",
                 policy_name="none")


def _make_prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=l).tolist() for l in lengths]


def _cfg_for(name):
    if name == "mqa":
        base = get_config("internlm2-1.8b_smoke")
        return dataclasses.replace(base, name="mqa_smoke", n_kv_heads=1)
    return get_config(name)


def _drained(engine):
    """Assert every pool is fully free and internally consistent."""
    for alloc in engine.allocators:
        alloc.check_invariant()
        assert alloc.free_pages == alloc.spec.n_pages, "pages leaked"


# ---------------------------------------------------------------------------
# kernel: paged gather vs the dense oracle
# ---------------------------------------------------------------------------
def _random_paging(k_dense, v_dense, spos, ps, n_pages, seed=0):
    """Scatter a dense cache into shuffled pages; returns pool + tables."""
    B, S, KV, dh = k_dense.shape
    nb = S // ps
    rng = np.random.default_rng(seed)
    k_pages = rng.standard_normal((n_pages, ps, KV, dh)).astype(k_dense.dtype)
    v_pages = rng.standard_normal((n_pages, ps, KV, dh)).astype(v_dense.dtype)
    page_pos = rng.integers(0, S, size=(n_pages, ps)).astype(np.int32)
    bt = np.full((B, nb), -1, np.int32)
    free = list(rng.permutation(n_pages))
    for b in range(B):
        n_valid = int((spos[b] >= 0).sum())
        for j in range(-(-n_valid // ps)):   # an empty slot maps no page
            p = free.pop()
            bt[b, j] = p
            k_pages[p] = k_dense[b, j * ps:(j + 1) * ps]
            v_pages[p] = v_dense[b, j * ps:(j + 1) * ps]
            page_pos[p] = spos[b, j * ps:(j + 1) * ps]
    return k_pages, v_pages, page_pos, bt


@pytest.mark.parametrize("B,S,H,KV,dh,ps,window", [
    (2, 64, 4, 2, 64, 16, 0),      # GQA
    (1, 96, 4, 1, 32, 8, 0),       # MQA
    (2, 32, 8, 2, 80, 8, 0),       # non-128 head dim
    (1, 16, 2, 2, 128, 8, 8),      # ring: window inside the logical size
    (2, 48, 4, 2, 64, 12, 0),      # page size not a sublane multiple
    (2, 256, 16, 8, 128, 128, 0),  # the serve cells' widths: G 2, 128-token pages
    (6, 384, 16, 8, 128, 128, 0),  # ... with slots of 0 (unmapped), 1, ps, ps+1
    (6, 49, 4, 1, 32, 7, 0),       # MQA at an odd page size, unpadded
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_paged_decode_vs_dense_ref(B, S, H, KV, dh, ps, window, dtype):
    from repro.kernels.flash_decode import (flash_decode_ref,
                                            flash_paged_decode_kernel,
                                            flash_paged_decode_ref)

    rng = np.random.default_rng(1)
    k = rng.standard_normal((B, S, KV, dh)).astype(np.float32)
    v = rng.standard_normal((B, S, KV, dh)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((B, 1, H, dh)), dtype)
    # slots past the second hold 0 tokens (block-table row all -1), 1,
    # page_size and page_size + 1 tokens, in turn
    n_valid = np.array(([S - 3, S // 2] + [0, 1, ps, ps + 1] * B)[:B])
    live = n_valid > 0
    spos = np.where(np.arange(S)[None] < n_valid[:, None],
                    np.arange(S)[None], -1).astype(np.int32)
    qpos = (n_valid - 1).astype(np.int32)
    kp, vp, ppos, bt = _random_paging(k, v, spos, ps, n_pages=2 + B * (S // ps))

    kd, vd = jnp.asarray(k, dtype), jnp.asarray(v, dtype)
    kpd, vpd = jnp.asarray(kp, dtype), jnp.asarray(vp, dtype)
    o_dense = flash_decode_ref(q, kd, vd, jnp.asarray(qpos), jnp.asarray(spos),
                               causal=True, window=window)
    o_ref = flash_paged_decode_ref(q, kpd, vpd, jnp.asarray(qpos),
                                   jnp.asarray(bt), jnp.asarray(ppos),
                                   causal=True, window=window)
    o_kern = flash_paged_decode_kernel(q, kpd, vpd, jnp.asarray(qpos),
                                       jnp.asarray(bt), jnp.asarray(ppos),
                                       causal=True, window=window,
                                       interpret=True)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o_ref, np.float32)[live],
                               np.asarray(o_dense, np.float32)[live], atol=tol)
    np.testing.assert_allclose(np.asarray(o_kern, np.float32)[live],
                               np.asarray(o_dense, np.float32)[live], atol=tol)
    # a slot with no mapped page attends to nothing: the kernel skips all
    # its steps and returns zeros
    assert np.all(np.asarray(o_kern, np.float32)[~live] == 0)


def test_paged_insert_matches_dense_insert():
    """One decode step's insert lands the same K/V rows whether it goes
    through the dense slab or the block table."""
    from repro.models.attention import (cache_insert, init_kv_cache,
                                        init_paged_kv_cache, paged_insert)

    B, S, KV, dh, ps = 3, 32, 2, 16, 8
    rng = np.random.default_rng(2)
    k_new = jnp.asarray(rng.standard_normal((B, 1, KV, dh)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((B, 1, KV, dh)), jnp.float32)
    positions = jnp.asarray([[5], [-1], [17]], jnp.int32)  # row 1 parked

    dense = cache_insert(init_kv_cache(B, S, KV, dh, jnp.float32, False),
                         k_new, v_new, positions)
    paged = init_paged_kv_cache(B, S, ps, n_pages=B * S // ps, kv=KV, dh=dh,
                                dtype=jnp.float32, ring=False)
    # identity-ish table: slot b owns pages [b*nb .. b*nb+nb)
    nb = S // ps
    bt = (np.arange(B)[:, None] * nb + np.arange(nb)[None]).astype(np.int32)
    paged = paged._replace(block_table=jnp.asarray(bt))
    paged = paged_insert(paged, k_new, v_new, positions)

    for b, p in ((0, 5), (2, 17)):
        np.testing.assert_array_equal(
            np.asarray(paged.k_pages[bt[b, p // ps], p % ps]),
            np.asarray(dense.k[b, p]))
        assert int(paged.page_pos[bt[b, p // ps], p % ps]) == p
    # parked row wrote nothing
    assert int((np.asarray(paged.page_pos) >= 0).sum()) == 2


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------
def test_allocator_alloc_release_append_invariant():
    spec = PoolSpec(page_size=8, n_pages=6, blocks_per_slot=4, ring=False,
                    token_bytes=4)
    a = PageAllocator(spec)
    assert a.blocks_for(1) == 1 and a.blocks_for(8) == 1
    assert a.blocks_for(9) == 2 and a.blocks_for(32) == 4
    with pytest.raises(ValueError, match="non-ring"):  # loud, not table-capped
        a.blocks_for(1000)

    row0 = a.allocate(0, 3)
    assert (row0 >= 0).sum() == 3 and a.free_pages == 3
    a.check_invariant()
    with pytest.raises(RuntimeError, match="already owns"):
        a.allocate(0, 1)
    row1 = a.allocate(1, 3)
    assert not a.can_allocate(1)
    with pytest.raises(RuntimeError, match="exhausted"):
        a.allocate(2, 1)
    a.check_invariant()

    assert a.release(0) == 3 and a.free_pages == 3
    row1b = a.append(1, 1)
    assert (row1b >= 0).sum() == 4
    with pytest.raises(RuntimeError, match="table full"):
        a.append(1, 1)
    a.check_invariant()
    assert a.release(1) == 4 and a.free_pages == 6
    a.check_invariant()
    assert a.release(1) == 0  # idempotent
    assert a.reserved_bytes == 0
    assert a.used_tokens(1000) == spec.logical_size  # ring-style clamp


def test_allocator_blocks_for_non_ring_overflow_raises():
    """Regression: blocks_for on a non-ring pool silently capped the
    answer at blocks_per_slot, so over-long requests were admitted with
    truncated reservations and trampled the cache. It must raise; only
    ring (sliding-window) pools legitimately cap at the table size."""
    spec = PoolSpec(page_size=8, n_pages=6, blocks_per_slot=4, ring=False,
                    token_bytes=4)
    a = PageAllocator(spec)
    assert a.blocks_for(32) == 4               # exactly the table
    with pytest.raises(ValueError, match="non-ring slot table holds"):
        a.blocks_for(33)                       # one token over
    ring = PageAllocator(PoolSpec(page_size=8, n_pages=6, blocks_per_slot=4,
                                  ring=True, token_bytes=4))
    assert ring.blocks_for(33) == 4            # ring wraps: cap is correct
    assert ring.blocks_for(10_000) == 4


# ---------------------------------------------------------------------------
# engine: paged == dense token parity
# ---------------------------------------------------------------------------
PARITY_ARCHS = [
    "internlm2-1.8b_smoke",            # GQA
    "mqa",                             # MQA (kv=1)
    "h2o-danube-3-4b_smoke",           # sliding-window ring cache
    "llama-3.2-vision-11b_smoke",      # vision prefill (xattn stays dense)
    "qwen3-32b_smoke",                 # qk-norm
]


@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_paged_engine_matches_dense(arch):
    """Same requests, same params: the paged engine's token streams are
    identical to the dense engine's, with a page size that forces multi-
    page sequences and mixed greedy/stochastic sampling."""
    cfg = _cfg_for(arch)
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    prompts = _make_prompts(cfg, [12, 7, 9], seed=3)
    rng = np.random.default_rng(4)
    imgs = (rng.standard_normal((3, cfg.vision_tokens, cfg.d_model)
                                ).astype(np.float32)
            if cfg.vision_tokens else [None] * 3)

    def reqs():
        return [Request(uid=i, tokens=prompts[i], max_new_tokens=6 + 2 * i,
                        sampling=SamplingParams(
                            temperature=0.7 if i == 1 else 0.0,
                            top_k=8 if i == 1 else 0, seed=40 + i),
                        image_embeds=imgs[i] if cfg.vision_tokens else None)
                for i in range(3)]

    dense = ServeEngine(cfg, RCFG, params, max_slots=2, max_len=48,
                        decode_block=4)
    out_d = dense.run(reqs())
    paged = ServeEngine(cfg, RCFG, params, max_slots=2, max_len=48,
                        decode_block=4, cache_layout="paged", page_size=8)
    out_p = paged.run(reqs())
    assert paged.allocators, "paged engine built no page pools"
    for i in range(3):
        assert out_p[i].tokens == out_d[i].tokens, f"request {i} diverged"
    _drained(paged)


def test_paged_engine_matches_solo_runs():
    """Continuous batching through a paged cache keeps the invariant:
    each request's tokens equal its solo run."""
    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    prompts = _make_prompts(cfg, [8, 11, 6, 14], seed=5)
    reqs = [Request(uid=i, tokens=prompts[i], max_new_tokens=4 + 3 * i,
                    sampling=SamplingParams(temperature=0.8 if i % 2 else 0.0,
                                            top_k=8 if i % 2 else 0,
                                            seed=100 + i))
            for i in range(4)]
    eng = ServeEngine(cfg, RCFG, params, max_slots=2, max_len=64,
                      decode_block=3, cache_layout="paged", page_size=8)
    batched = eng.run(reqs)
    for i, req in enumerate(reqs):
        solo = ServeEngine(cfg, RCFG, params, max_slots=1, max_len=64,
                           decode_block=3, cache_layout="paged",
                           page_size=8).run([req])[i]
        assert solo.tokens == batched[i].tokens, f"request {i} diverged"
    _drained(eng)


# ---------------------------------------------------------------------------
# engine: churn, reuse, admission gating
# ---------------------------------------------------------------------------
def test_paged_churn_reuses_pages_and_never_leaks():
    """Admit/evict/readmit through a pool far smaller than the dense
    worst case: every page is recycled across owners (lifetime
    allocations exceed the pool), the free-xor-owned invariant holds at
    every step, and the tokens still match the dense engine."""
    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    lens = [6, 9, 7, 10, 6, 8, 11, 6, 9, 7]
    prompts = _make_prompts(cfg, lens, seed=6)
    mk = lambda: [Request(uid=i, tokens=prompts[i], max_new_tokens=5)
                  for i in range(len(prompts))]

    out_d = ServeEngine(cfg, RCFG, params, max_slots=3, max_len=64,
                        decode_block=3).run(mk())
    eng = ServeEngine(cfg, RCFG, params, max_slots=3, max_len=64,
                      decode_block=3, cache_layout="paged", page_size=8,
                      pool_tokens=48)  # 6 pages vs worst case 24
    for r in mk():
        eng.submit(r)
    done = {}
    while eng.has_work:
        for out in eng.step():
            done[out.uid] = out
        for alloc in eng.allocators:
            alloc.check_invariant()
    for i in range(len(prompts)):
        assert done[i].tokens == out_d[i].tokens, f"request {i} diverged"
    _drained(eng)
    for alloc in eng.allocators:
        assert alloc.total_page_allocations > alloc.spec.n_pages, \
            "churn never recycled a page — pool too large for the test"


def test_paged_admission_waits_for_pages():
    """With pages for only ~one request in flight, requests serialize but
    all complete, and concurrency never exceeds what the pool can back."""
    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    prompts = _make_prompts(cfg, [10, 9, 8], seed=7)
    mk = lambda: [Request(uid=i, tokens=prompts[i], max_new_tokens=6)
                  for i in range(3)]
    out_d = ServeEngine(cfg, RCFG, params, max_slots=3, max_len=64,
                        decode_block=4).run(mk())
    eng = ServeEngine(cfg, RCFG, params, max_slots=3, max_len=64,
                      decode_block=4, cache_layout="paged", page_size=8,
                      pool_tokens=16)  # 2 pages = one 10+6-token request
    out_p = eng.run(mk())
    for i in range(3):
        assert out_p[i].tokens == out_d[i].tokens
    assert eng.peak_active == 1, "pool for one request admitted several"
    _drained(eng)


def test_submit_rejects_request_larger_than_pool():
    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    eng = ServeEngine(cfg, RCFG, params, max_slots=2, max_len=64,
                      decode_block=4, cache_layout="paged", page_size=8,
                      pool_tokens=16)
    with pytest.raises(ValueError, match="raise pool_tokens"):
        eng.submit(Request(uid=0, tokens=list(range(30)), max_new_tokens=20))


def test_paged_on_mesh_matches_single_host():
    """Paged serving on a (1-device) mesh: the per-replica sharded pool
    path produces the same tokens as the plain single-host engine.
    (Real multi-device shard parity lives in tests/test_multidevice.py.)"""
    from jax.sharding import Mesh

    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    prompts = _make_prompts(cfg, [10, 7], seed=11)
    mk = lambda: [Request(uid=i, tokens=prompts[i], max_new_tokens=5)
                  for i in range(2)]
    solo = ServeEngine(cfg, RCFG, params, max_slots=2, max_len=64,
                       decode_block=4, cache_layout="paged",
                       page_size=8).run(mk())
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    eng = ServeEngine(cfg, RCFG, params, max_slots=2, max_len=64,
                      decode_block=4, cache_layout="paged", page_size=8,
                      mesh=mesh)
    out = eng.run(mk())
    for i in range(2):
        assert out[i].tokens == solo[i].tokens
    assert eng.n_replicas == 1
    _drained(eng)


# ---------------------------------------------------------------------------
# prefill bucketing
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_prefill_buckets_compile_once_per_bucket(layout):
    """Prompt lengths 17..23 share the 32 bucket: one prefill compile,
    and the engine's tracked bucket set says so."""
    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    prompts = _make_prompts(cfg, [17, 19, 21, 23], seed=8)
    eng = ServeEngine(cfg, RCFG, params, max_slots=2, max_len=64,
                      decode_block=4, cache_layout=layout, page_size=8)
    assert eng.prefill_buckets
    eng.run([Request(uid=i, tokens=p, max_new_tokens=4)
             for i, p in enumerate(prompts)])
    assert eng.stats()["prefill_compiles"] == 1
    if hasattr(eng._prefill_fn, "_cache_size"):
        assert eng._prefill_fn._cache_size() == 1


def test_bucketing_disabled_for_recurrent_archs():
    """rec/ssm prefill state is sequence-coupled: pad tokens would change
    the spliced recurrent state, so those archs opt out automatically."""
    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    eng = ServeEngine(cfg, RCFG, params, max_slots=1, max_len=32)
    assert eng.prefill_buckets
    rcfg_cfg = get_config("recurrentgemma-9b_smoke")
    rparams, _ = init_model(rcfg_cfg, RCFG, jax.random.key(0))
    reng = ServeEngine(rcfg_cfg, RCFG, rparams, max_slots=1, max_len=32)
    assert not reng.prefill_buckets


def test_bucketed_splice_ignores_pad_rows():
    """After admitting a bucketed prompt, no cache row beyond the true
    prompt length is live — dense slot_pos and paged page_pos agree."""
    from repro.serve.cache import kv_cache_nodes, read_slot

    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    lp = 19  # buckets to 32
    prompt = _make_prompts(cfg, [lp], seed=9)[0]

    dense = ServeEngine(cfg, RCFG, params, max_slots=1, max_len=64,
                        decode_block=4)
    dense._admit(Request(uid=0, tokens=prompt, max_new_tokens=8), 0)
    for node in kv_cache_nodes(read_slot(dense.caches, 0)):
        spos = np.asarray(node.slot_pos)
        assert spos.max() == lp - 1, "pad rows leaked into the dense splice"
        assert int((spos >= 0).sum()) == lp * node.slot_pos.shape[0]

    paged = ServeEngine(cfg, RCFG, params, max_slots=1, max_len=64,
                        decode_block=4, cache_layout="paged", page_size=8)
    paged._admit(Request(uid=0, tokens=prompt, max_new_tokens=8), 0)
    for node, alloc in zip(kv_cache_nodes(paged.caches), paged.allocators):
        row = alloc.owned_row(0)
        owned = row[row >= 0]
        ppos = np.asarray(node.page_pos)[:, owned]  # (layers, n_owned, ps)
        assert ppos.max() == lp - 1, "pad rows leaked into the paged splice"
        assert int((ppos >= 0).sum()) == lp * node.page_pos.shape[0]


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_cache_telemetry_reserved_vs_used(layout):
    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    eng = ServeEngine(cfg, RCFG, params, max_slots=2, max_len=64,
                      decode_block=2, cache_layout=layout, page_size=8)
    prompts = _make_prompts(cfg, [10, 12], seed=10)
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, tokens=p, max_new_tokens=8))
    eng.step()
    tel = eng.cache_telemetry()
    assert tel["cache/kv_used_mb"] > 0
    assert tel["cache/kv_reserved_mb"] >= tel["cache/kv_used_mb"]
    assert tel["cache/kv_capacity_mb"] >= tel["cache/kv_reserved_mb"]
    if layout == "paged":
        assert tel["cache/kv_pages_total"] > tel["cache/kv_pages_free"] > 0
        # paged reserves ceil((prompt+gen)/page) pages, not max_len slabs
        assert tel["cache/kv_reserved_mb"] < tel["cache/kv_capacity_mb"]
    else:
        # dense reserves the whole slab per occupied slot
        assert tel["cache/kv_reserved_mb"] == tel["cache/kv_capacity_mb"]
    while eng.has_work:
        eng.step()
    end = eng.cache_telemetry()
    assert end["cache/kv_reserved_mb"] == 0.0
    assert eng.stats()["peak_kv_reserved_bytes"] >= \
        eng.stats()["peak_kv_used_bytes"] > 0
