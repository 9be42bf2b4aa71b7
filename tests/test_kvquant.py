"""Cache-side compression tests (ISSUE 6), mirroring test_paging.py.

Covers: the cache-site CompressionPlan grammar (cache.kv=int8 | int4 |
svd rules resolved next to training sites); absmax quantize/dequant and
int4 nibble packing roundtrips; the fused-dequant paged decode kernel and
jnp oracle against the dense oracle running on dequantized values (the
quantization itself is the only error source, and the kernel adds none);
quantize-on-insert and the quantized prefill splice against the reference
quantizer; svd full-rank exactness and low-rank logit tolerance;
compressed-pool byte accounting (same pool budget -> proportionally more
pages, true compressed reserved bytes); engine greedy parity int8 == fp32
paged on the parity archs and batched == solo under quantized churn; the
compression telemetry; and the actionable shard_slots / submit errors.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import RunConfig, get_config
from repro.core.plan import CacheFormat, CompressionPlan, cache_plan_from_spec
from repro.models import init_caches, init_model, prefill
from repro.serve import Request, SamplingParams, ServeEngine

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
    for alloc in engine.allocators:
        alloc.check_invariant()
        assert alloc.free_pages == alloc.spec.n_pages, "pages leaked"


# ---------------------------------------------------------------------------
# plan grammar: cache sites
# ---------------------------------------------------------------------------
def test_cache_plan_grammar_resolves_cache_sites():
    cfg = get_config("internlm2-1.8b_smoke")
    for spec, kind in [("int8", "int8"), ("cache.kv=int8", "int8"),
                       ("int4(group=8)", "int4"), ("svd(r=1/4)", "svd")]:
        resolved = cache_plan_from_spec(spec).resolve(cfg)
        sites = resolved.compressed_cache_sites
        assert len(sites) == 1 and sites[0].fmt.kind == kind, spec
        assert sites[0].path == "stage0.attn.cache.kv"
        fmt = resolved.cache_format(0, "attn")
        assert fmt is not None and fmt.kind == kind


def test_cache_rules_do_not_touch_training_sites_and_vice_versa():
    cfg = get_config("internlm2-1.8b_smoke")
    plan = CompressionPlan.parse("attn.qkv=pamm(r=1/512);cache.kv=int8")
    resolved = plan.resolve(cfg)
    # training site got pamm, cache site got int8 — independent taxonomies
    assert any(s.policy.name == "pamm" for s in resolved.sites)
    assert all(s.policy.name != "int8" for s in resolved.sites)
    assert resolved.compressed_cache_sites[0].fmt.kind == "int8"
    # fp aliases reset a cache rule; plain none does too
    for spec in ("cache.kv=fp16", "cache.kv=none",
                 "cache.kv=int8;cache.kv=none"):  # last-match-wins reset
        r = cache_plan_from_spec(spec).resolve(cfg)
        assert not r.compressed_cache_sites, spec


def test_cache_format_validation_and_token_bytes():
    with pytest.raises(ValueError, match="power of two"):
        CacheFormat("int8", group=3)
    with pytest.raises(ValueError):
        CacheFormat("svd", rank=0.0)
    # smoke dims: kv=2, dh=16, fp32 -> dense 256 B/token (one layer)
    dense = CacheFormat("none").token_bytes(2, 16, 4)
    assert dense == 2 * 2 * 16 * 4
    i8 = CacheFormat("int8").token_bytes(2, 16, 4)
    assert i8 == 2 * 2 * (16 + 4) and dense / i8 == 3.2
    i4 = CacheFormat("int4", group=64).token_bytes(2, 16, 4)  # clamps to dh
    assert i4 == 2 * 2 * (8 + 4)
    svd = CacheFormat("svd", rank=0.25).token_bytes(2, 16, 4)
    assert svd == 2 * 2 * 4 * 4 and dense / svd == 4.0


# ---------------------------------------------------------------------------
# quantizer math
# ---------------------------------------------------------------------------
def test_quantize_roundtrip_error_bounds():
    from repro.kernels.flash_decode import dequantize_kv, quantize_kv

    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((2, 5, 3, 16)) * 3.0, jnp.float32)
    for bits, ngr in [(8, 1), (8, 4), (4, 1), (4, 2)]:
        q, s = quantize_kv(x, bits, ngr)
        assert q.dtype == jnp.int8
        assert q.shape[-1] == (16 if bits == 8 else 8)
        assert s.shape == x.shape[:-1] + (ngr,)
        err = np.abs(np.asarray(dequantize_kv(q, s, 16) - x))
        # absmax symmetric quant: per element, |err| <= its group's scale/2
        bound = np.repeat(np.asarray(s), 16 // ngr, axis=-1) * 0.5 + 1e-6
        assert (err <= bound).all(), (bits, ngr, (err - bound).max())


def test_int4_pack_unpack_exact():
    from repro.kernels.flash_decode import pack_int4, unpack_int4

    vals = jnp.asarray(np.arange(-7, 8, dtype=np.int8)[None].repeat(2, 0)
                       [:, :14], jnp.int8)  # even width
    packed = pack_int4(vals)
    assert packed.shape[-1] == 7
    np.testing.assert_array_equal(np.asarray(unpack_int4(packed)),
                                  np.asarray(vals))


# ---------------------------------------------------------------------------
# kernel: fused-dequant paged gather vs the dense oracle on dequant values
# ---------------------------------------------------------------------------
def _random_quant_paging(k, v, spos, ps, n_pages, bits, ngr, seed=0):
    """Quantize a dense cache and scatter it through a shuffled table."""
    from repro.kernels.flash_decode import quantize_kv

    B, S, KV, dh = k.shape
    nb = S // ps
    dhq = dh if bits == 8 else dh // 2
    rng = np.random.default_rng(seed)
    k_pages = rng.integers(-8, 8, size=(n_pages, ps, KV, dhq)).astype(np.int8)
    v_pages = rng.integers(-8, 8, size=(n_pages, ps, KV, dhq)).astype(np.int8)
    k_scale = rng.random((n_pages, ps, KV, ngr)).astype(np.float32)
    v_scale = rng.random((n_pages, ps, KV, ngr)).astype(np.float32)
    page_pos = rng.integers(0, S, size=(n_pages, ps)).astype(np.int32)
    bt = np.full((B, nb), -1, np.int32)
    kq, ks = (np.asarray(a) for a in quantize_kv(jnp.asarray(k), bits, ngr))
    vq, vs = (np.asarray(a) for a in quantize_kv(jnp.asarray(v), bits, ngr))
    free = list(rng.permutation(n_pages))
    for b in range(B):
        n_valid = int((spos[b] >= 0).sum())
        for j in range(-(-max(n_valid, 1) // ps)):
            p = free.pop()
            bt[b, j] = p
            sl = slice(j * ps, (j + 1) * ps)
            k_pages[p], v_pages[p] = kq[b, sl], vq[b, sl]
            k_scale[p], v_scale[p] = ks[b, sl], vs[b, sl]
            page_pos[p] = spos[b, sl]
    return k_pages, v_pages, k_scale, v_scale, page_pos, bt


@pytest.mark.parametrize("B,S,H,KV,dh,ps,window,bits,ngr", [
    (2, 64, 4, 2, 64, 16, 0, 8, 1),    # GQA int8, per-token scale
    (1, 96, 4, 1, 32, 8, 0, 8, 4),     # MQA int8, grouped scales
    (2, 32, 8, 2, 80, 8, 0, 8, 5),     # non-128 head dim, 5 groups
    (1, 16, 2, 2, 128, 8, 8, 4, 8),    # ring window, int4 grouped
    (2, 48, 4, 2, 64, 12, 0, 4, 1),    # int4 per-token, ps pads to 16
])
def test_flash_paged_decode_quant_vs_dequant_oracle(B, S, H, KV, dh, ps,
                                                    window, bits, ngr):
    """The fused-dequant kernel must add NO error beyond quantization:
    compare against the dense oracle fed the dequantized cache."""
    from repro.kernels.flash_decode import (dequantize_kv, flash_decode_ref,
                                            flash_paged_decode_quant_kernel,
                                            flash_paged_decode_quant_ref)

    rng = np.random.default_rng(21)
    k = rng.standard_normal((B, S, KV, dh)).astype(np.float32)
    v = rng.standard_normal((B, S, KV, dh)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((B, 1, H, dh)), jnp.float32)
    n_valid = np.array([S - 3, S // 2][:B][:B] + [S] * max(0, B - 2))[:B]
    spos = np.where(np.arange(S)[None] < n_valid[:, None],
                    np.arange(S)[None], -1).astype(np.int32)
    qpos = (n_valid - 1).astype(np.int32)
    kp, vp, ks, vs, ppos, bt = _random_quant_paging(
        k, v, spos, ps, n_pages=2 + B * (S // ps), bits=bits, ngr=ngr)

    # dense oracle on the dequantized rows at the same addresses
    kd = np.zeros_like(k)
    vd = np.zeros_like(v)
    for b in range(B):
        for j, p in enumerate(bt[b]):
            if p < 0:
                continue
            sl = slice(j * ps, (j + 1) * ps)
            kd[b, sl] = np.asarray(dequantize_kv(
                jnp.asarray(kp[p]), jnp.asarray(ks[p]), dh))
            vd[b, sl] = np.asarray(dequantize_kv(
                jnp.asarray(vp[p]), jnp.asarray(vs[p]), dh))
    o_dense = flash_decode_ref(q, jnp.asarray(kd), jnp.asarray(vd),
                               jnp.asarray(qpos), jnp.asarray(spos),
                               causal=True, window=window)
    args = (q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(ks),
            jnp.asarray(vs), jnp.asarray(qpos), jnp.asarray(bt),
            jnp.asarray(ppos))
    o_ref = flash_paged_decode_quant_ref(*args, causal=True, window=window)
    o_kern = flash_paged_decode_quant_kernel(*args, causal=True,
                                             window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_dense),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(o_kern), np.asarray(o_dense),
                               atol=2e-5)


# ---------------------------------------------------------------------------
# quantize-on-insert and splice
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("bits,ngr", [(8, 1), (8, 2), (4, 1), (4, 2)])
def test_paged_insert_quant_matches_reference_quantizer(bits, ngr):
    from repro.kernels.flash_decode import quantize_kv
    from repro.models.attention import (init_quant_paged_kv_cache,
                                        paged_insert_quant)

    B, S, KV, dh, ps = 3, 32, 2, 16, 8
    rng = np.random.default_rng(22)
    k_new = jnp.asarray(rng.standard_normal((B, 1, KV, dh)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((B, 1, KV, dh)), jnp.float32)
    positions = jnp.asarray([[5], [-1], [17]], jnp.int32)  # row 1 parked

    cache = init_quant_paged_kv_cache(B, S, ps, n_pages=B * S // ps, kv=KV,
                                      dh=dh, bits=bits, ngr=ngr, ring=False)
    nb = S // ps
    bt = (np.arange(B)[:, None] * nb + np.arange(nb)[None]).astype(np.int32)
    cache = cache._replace(block_table=jnp.asarray(bt))
    cache = paged_insert_quant(cache, k_new, v_new, positions, dh)

    kq, ks = quantize_kv(k_new, bits, ngr)
    for b, p in ((0, 5), (2, 17)):
        pg, off = bt[b, p // ps], p % ps
        np.testing.assert_array_equal(np.asarray(cache.k_pages[pg, off]),
                                      np.asarray(kq[b, 0]))
        np.testing.assert_array_equal(np.asarray(cache.k_scale[pg, off]),
                                      np.asarray(ks[b, 0]))
        assert int(cache.page_pos[pg, off]) == p
    assert int((np.asarray(cache.page_pos) >= 0).sum()) == 2  # parked row


def test_quant_splice_matches_insert_path():
    """Splicing a prefill cache into a quant pool stores the SAME bytes the
    decode-time quantize-on-write would: one quantizer, two entry points."""
    from repro.kernels.flash_decode import quantize_kv
    from repro.serve.cache import kv_cache_nodes

    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    lp = 8
    toks = jnp.asarray(_make_prompts(cfg, [lp], seed=23)[0])[None]
    _, pc = prefill(cfg, RCFG, params, {"tokens": toks}, 32, None,
                    prompt_len=jnp.asarray([lp], jnp.int32))

    eng = ServeEngine(cfg, RCFG, params, max_slots=1, max_len=32,
                      cache_layout="paged", page_size=8,
                      cache_compress="int8")
    eng._admit(Request(uid=0, tokens=np.asarray(toks[0]).tolist(),
                       max_new_tokens=4), 0)
    [dense_node] = list(kv_cache_nodes(pc))
    [quant_node] = list(kv_cache_nodes(eng.caches))
    [alloc] = eng.allocators
    row = alloc.owned_row(0)
    kq, ks = quantize_kv(dense_node.k[:, 0], 8, 1)  # (layers, S, KV, dh)
    for pos in range(lp):
        pg, off = int(row[pos // 8]), pos % 8
        np.testing.assert_array_equal(
            np.asarray(quant_node.k_pages[:, pg, off]),
            np.asarray(kq[:, pos]))
        np.testing.assert_allclose(          # jit vs eager: 1-ulp scales
            np.asarray(quant_node.k_scale[:, pg, off]),
            np.asarray(ks[:, pos]), rtol=1e-6)


# ---------------------------------------------------------------------------
# svd pools
# ---------------------------------------------------------------------------
def test_svd_full_rank_engine_matches_fp_paged_exactly():
    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    prompts = _make_prompts(cfg, [9, 12, 7], seed=24)
    mk = lambda: [Request(uid=i, tokens=prompts[i], max_new_tokens=8)
                  for i in range(3)]
    base = ServeEngine(cfg, RCFG, params, max_slots=2, max_len=48,
                       decode_block=4, cache_layout="paged", page_size=8)
    out_b = base.run(mk())
    svd = ServeEngine(cfg, RCFG, params, max_slots=2, max_len=48,
                      decode_block=4, cache_layout="paged", page_size=8,
                      cache_compress="svd(r=1.0)")
    out_s = svd.run(mk())
    for i in range(3):
        assert out_s[i].tokens == out_b[i].tokens, f"request {i} diverged"
    _drained(svd)


def test_svd_bases_are_orthonormal_and_weight_aligned():
    from repro.models.attention import SVDPagedKVCache
    from repro.serve.cache import kv_cache_nodes

    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    eng = ServeEngine(cfg, RCFG, params, max_slots=1, max_len=32,
                      cache_layout="paged", page_size=8,
                      cache_compress="svd(r=0.5)")
    [node] = [n for n in kv_cache_nodes(eng.caches)
              if isinstance(n, SVDPagedKVCache)]
    layers, _, _, kv, r = node.k_pages.shape
    assert r == cfg.head_dim // 2
    kb = np.asarray(node.k_basis)                      # (layers, kv, dh, r)
    assert kb.shape == (layers, kv, cfg.head_dim, r)
    eye = np.eye(r)
    for l in range(layers):
        for h in range(kv):
            np.testing.assert_allclose(kb[l, h].T @ kb[l, h], eye, atol=1e-5)
    # not the init-time identity prefix: install_svd_bases ran
    assert not np.allclose(kb[0, 0], np.eye(cfg.head_dim, r))


# ---------------------------------------------------------------------------
# engine parity
# ---------------------------------------------------------------------------
PARITY_ARCHS = [
    ("internlm2-1.8b_smoke", 5),       # GQA
    ("mqa", 5),                        # MQA (kv=1)
    ("h2o-danube-3-4b_smoke", 5),      # sliding-window ring cache
    ("llama-3.2-vision-11b_smoke", 17),  # vision prefill (xattn dense)
    ("qwen3-32b_smoke", 5),            # qk-norm
]


# int8 KV moves any one decode logit by less than this (the int8 bound of
# test_compressed_decode_logits_within_tolerance below).
INT8_LOGIT_TOL = 0.15


def _parity_reqs(cfg, imgs, base):
    # fixed prompts and lengths, with a per-arch prompt base
    return [Request(uid=i, tokens=list(range(base, base + 8 + i)),
                    max_new_tokens=8, sampling=SamplingParams(),
                    image_embeds=imgs[i] if cfg.vision_tokens else None)
            for i in range(3)]


@pytest.mark.parametrize("arch,base", PARITY_ARCHS)
def test_int8_engine_greedy_matches_fp_paged(arch, base):
    cfg = _cfg_for(arch)
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    rng = np.random.default_rng(25)
    imgs = (rng.standard_normal((3, cfg.vision_tokens, cfg.d_model)
                                ).astype(np.float32)
            if cfg.vision_tokens else [None] * 3)

    base_eng = ServeEngine(cfg, RCFG, params, max_slots=2, max_len=48,
                           decode_block=4, cache_layout="paged", page_size=8)
    out_b = base_eng.run(_parity_reqs(cfg, imgs, base))
    q8 = ServeEngine(cfg, RCFG, params, max_slots=2, max_len=48,
                     decode_block=4, cache_layout="paged", page_size=8,
                     cache_compress="int8")
    reqs = _parity_reqs(cfg, imgs, base)
    out_q = q8.run(reqs)
    # Greedy over int8 logits can only pick another token than fp greedy
    # where the fp logits of the two tokens lie within 2 * INT8_LOGIT_TOL:
    # the tokens agree up to the first such near-tie, which is checked
    # against fp logits of the shared prefix (random-init smoke models
    # have near-ties that int8 noise may flip).
    for i, req in enumerate(reqs):
        fp, q8_toks = out_b[i].tokens, out_q[i].tokens
        assert len(fp) == len(q8_toks) == req.max_new_tokens
        j = next((j for j, (a, b) in enumerate(zip(fp, q8_toks)) if a != b),
                 None)
        if j is None:
            continue
        batch = {"tokens": jnp.asarray([req.tokens + fp[:j]], jnp.int32)}
        if cfg.vision_tokens:
            batch["image_embeds"] = jnp.asarray(imgs[i])[None]
        logits, _ = prefill(cfg, RCFG, params, batch, 48)
        margin = float(logits[0, -1, fp[j]] - logits[0, -1, q8_toks[j]])
        assert abs(margin) <= 2 * INT8_LOGIT_TOL, (
            f"request {i} step {j}: int8 picked token {q8_toks[j]} over "
            f"{fp[j]}, fp logit margin {margin}")
    _drained(q8)


@pytest.mark.parametrize("spec,tol", [
    ("int8", 0.15), ("int4", 1.5), ("int4(group=8)", 1.0),
    ("svd(r=0.5)", 8.0), ("svd(r=1.0)", 1e-4),
])
@pytest.mark.parametrize("arch", ["internlm2-1.8b_smoke",
                                  "h2o-danube-3-4b_smoke",
                                  "qwen3-32b_smoke"])
def test_compressed_decode_logits_within_tolerance(arch, spec, tol):
    """One spliced decode step: compressed-cache logits stay within a
    format-specific tolerance of the fp paged logits (the int4/svd
    acceptance bound; int8's is an order tighter)."""
    from repro.core.plan import cache_plan_from_spec as cpfs
    from repro.models import decode_step
    from repro.models.attention import SVDPagedKVCache
    from repro.serve import cache as cache_lib

    cfg = get_config(arch)
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    lp = 8
    toks = jnp.arange(2, 2 + lp)[None]
    _, pc = prefill(cfg, RCFG, params, {"tokens": toks}, 48, None,
                    prompt_len=jnp.asarray([lp], jnp.int32))

    def spliced_logits(spec_):
        plan = cpfs(spec_).resolve(cfg)
        full = init_caches(cfg, RCFG, 2, 48, layout="paged", page_size=8,
                           cache_plan=plan)
        if any(isinstance(n, SVDPagedKVCache)
               for n in cache_lib.kv_cache_nodes(full)):
            full = cache_lib.install_svd_bases(full, params, cfg)
        rows = []
        for st in full:
            rows.append([jnp.arange(n.block_table.shape[2], dtype=jnp.int32)
                         for n in st])
        full = cache_lib.write_slot_paged(full, pc, rows, jnp.int32(0),
                                          jnp.int32(lp))
        pos = jnp.asarray([[lp], [-1]], jnp.int32)
        lg, _ = decode_step(cfg, RCFG, params,
                            jnp.asarray([[5], [0]], jnp.int32), pos, full)
        return lg[0, 0, :cfg.vocab_size]

    ref = spliced_logits("")
    err = float(jnp.max(jnp.abs(spliced_logits(spec) - ref)))
    assert err < tol, f"{arch} {spec}: logit err {err} >= {tol}"


def test_quant_churn_batched_matches_solo_and_never_leaks():
    """Row independence survives compression: a request's tokens through a
    churning int8 pool equal its solo run through an identical engine,
    with every page recycled and the free-xor-owned invariant held."""
    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    lens = [6, 9, 7, 10, 6, 8, 11, 6, 9, 7]
    prompts = _make_prompts(cfg, lens, seed=26)
    mk = lambda: [Request(uid=i, tokens=prompts[i], max_new_tokens=5)
                  for i in range(len(prompts))]
    kw = dict(max_len=64, decode_block=3, cache_layout="paged",
              page_size=8, cache_compress="int8")
    eng = ServeEngine(cfg, RCFG, params, max_slots=3, pool_tokens=48, **kw)
    for r in mk():
        eng.submit(r)
    done = {}
    while eng.has_work:
        for out in eng.step():
            done[out.uid] = out
        for alloc in eng.allocators:
            alloc.check_invariant()
    for i, req in enumerate(mk()):
        solo = ServeEngine(cfg, RCFG, params, max_slots=1,
                           **kw).run([req])[i]
        assert done[i].tokens == solo.tokens, f"request {i} diverged"
    _drained(eng)
    for alloc in eng.allocators:
        assert alloc.total_page_allocations > alloc.spec.n_pages, \
            "churn never recycled a page — pool too large for the test"


# ---------------------------------------------------------------------------
# byte accounting and telemetry
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("spec,ratio", [
    ("int8", 3.2), ("int4", 16 / 3), ("svd(r=1/4)", 4.0),
])
def test_compressed_pool_grows_with_compression_ratio(spec, ratio):
    """Same pool_tokens byte budget: a compressed pool mints ~ratio x the
    fp page count, and its PoolSpec carries the true compressed
    token_bytes (smoke dims: kv=2, dh=16, fp32 -> 256 B dense/token/layer
    pair; int8 80 B, int4 48 B, svd(r=4) 64 B)."""
    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    kw = dict(max_slots=8, max_len=128, cache_layout="paged", page_size=8,
              pool_tokens=128)
    fp = ServeEngine(cfg, RCFG, params, **kw)
    cm = ServeEngine(cfg, RCFG, params, cache_compress=spec, **kw)
    [a_fp], [a_cm] = fp.allocators, cm.allocators
    assert a_cm.spec.n_pages == int(a_fp.spec.n_pages * ratio)
    assert a_cm.spec.token_bytes * ratio == a_fp.spec.token_bytes
    assert cm.kv_compression_x == pytest.approx(ratio)
    tel = cm.cache_telemetry()
    assert tel["cache/kv_compression_x"] == pytest.approx(ratio)
    assert fp.cache_telemetry()["cache/kv_compression_x"] == 1.0
    # per-pool telemetry names the format
    pool = cm.stats()["cache_pools"]["stage0.attn"]
    assert pool["format"].startswith(spec.split("(")[0])
    assert pool["token_bytes"] == a_cm.spec.token_bytes


def test_compressed_reserved_bytes_are_true_compressed_bytes():
    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    kw = dict(max_slots=2, max_len=64, decode_block=2,
              cache_layout="paged", page_size=8)
    fp = ServeEngine(cfg, RCFG, params, **kw)
    q8 = ServeEngine(cfg, RCFG, params, cache_compress="int8", **kw)
    req = lambda: [Request(uid=0, tokens=list(range(2, 12)),
                           max_new_tokens=6)]
    for eng in (fp, q8):
        for r in req():
            eng.submit(r)
        eng.step()
    t_fp, t_q8 = fp.cache_telemetry(), q8.cache_telemetry()
    assert 0 < t_q8["cache/kv_reserved_mb"] < t_fp["cache/kv_reserved_mb"]
    assert t_q8["cache/kv_reserved_mb"] == pytest.approx(
        t_fp["cache/kv_reserved_mb"] / 3.2)
    assert 0 < t_q8["cache/kv_used_mb"] < t_fp["cache/kv_used_mb"]


def test_pool_caps_at_dense_worst_case():
    """A compressed pool never allocates beyond every-slot-full: the
    page multiplier caps at the dense worst case."""
    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    eng = ServeEngine(cfg, RCFG, params, max_slots=2, max_len=32,
                      cache_layout="paged", page_size=8,
                      pool_tokens=10_000, cache_compress="int8")
    [alloc] = eng.allocators
    assert alloc.spec.n_pages == 2 * (32 // 8)  # B * blocks_per_slot


# ---------------------------------------------------------------------------
# actionable errors (ISSUE 6 satellites)
# ---------------------------------------------------------------------------
def test_submit_rejection_names_pool_and_token_deficit():
    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    eng = ServeEngine(cfg, RCFG, params, max_slots=2, max_len=64,
                      decode_block=4, cache_layout="paged", page_size=8,
                      pool_tokens=16)
    with pytest.raises(ValueError) as ei:
        eng.submit(Request(uid=7, tokens=list(range(30)), max_new_tokens=20))
    msg = str(ei.value)
    assert "request 7" in msg
    assert "50 tokens" in msg                  # requested: 30 + 20
    assert "stage0.attn" in msg                # which pool
    assert "2 pages (16 tokens)" in msg        # pool capacity
    assert "34 tokens over capacity" in msg    # the deficit
    assert "raise pool_tokens" in msg          # the remedy


def test_quant_paged_on_mesh_matches_single_host():
    """int8 page pools shard per replica on a mesh (serve/cache.shard_slots)
    and the quantized sharded decode path emits the same tokens as the
    single-host engine. (Pool divisibility errors are exercised on a real
    multi-device mesh in tests/test_multidevice.py.)"""
    from jax.sharding import Mesh

    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    prompts = _make_prompts(cfg, [9, 6], seed=13)
    mk = lambda: [Request(uid=i, tokens=prompts[i], max_new_tokens=5)
                  for i in range(2)]
    kw = dict(max_slots=2, max_len=64, decode_block=4, cache_layout="paged",
              page_size=8, cache_compress="int8")
    solo = ServeEngine(cfg, RCFG, params, **kw).run(mk())
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    eng = ServeEngine(cfg, RCFG, params, mesh=mesh, **kw)
    out = eng.run(mk())
    for i in range(2):
        assert out[i].tokens == solo[i].tokens


def test_cache_compress_requires_paged_layout():
    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    with pytest.raises(ValueError, match="cache_layout='paged'"):
        ServeEngine(cfg, RCFG, params, max_slots=1, max_len=32,
                    cache_compress="int8")


def test_cache_compress_spec_errors_early():
    cfg = get_config("internlm2-1.8b_smoke")
    params, _ = init_model(cfg, RCFG, jax.random.key(0))
    with pytest.raises(ValueError):
        ServeEngine(cfg, RCFG, params, max_slots=1, max_len=32,
                    cache_layout="paged", page_size=8,
                    cache_compress="int3")
