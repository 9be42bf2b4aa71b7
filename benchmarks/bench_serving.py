"""Serving engine benchmark: prefill tok/s, decode tok/s (fused-scan vs
the legacy per-token Python loop), and p50/p95 per-token decode latency.

The per-token loop is measured two ways: *stream* materializes every
token on the host (what per-token serving costs — tokens must reach the
host to be emitted and checked for stop conditions, which is the work
the engine actually does), and *async* is the seed loop verbatim
(device-resident tokens, dispatch overlapped with compute, but nothing
observable per step). The acceptance ratio — fused >= 3x — is against
the streaming loop; the async ratio is reported alongside. Token
streams of all paths are asserted identical before any timing.

``run_paged_mixed`` (registered as ``serving_paged_mixed``) is the paged
KV-cache acceptance workload: a mixed-prompt-length request set against a
FIXED KV pool budget, comparing max admissible concurrency and reserved
cache bytes between ``cache_layout=dense`` (whole max_len slabs) and
``paged`` (block tables). Token parity paged == dense is asserted first.

``run_paged_kvquant`` (``serving_kvquant``) repeats that workload with
compressed pools (``cache.kv=int8|int4|svd``) at the same pool byte
budget: acceptance is int8 admitting >= 1.8x the fp paged concurrency
(results persisted to BENCH_serving_kvquant.json by run.py).

``run_disagg`` (``serving_disagg``) benchmarks the disaggregated stage
API per stage (prefill / insert / generate) and the Router's replica
scaling: aggregate admissible concurrency must grow >= 3x from 1 to 4
decode replicas at a fixed per-replica pool budget, tokens identical to
the solo engine.

``run_prefix_shared`` (``serving_prefix_shared``) is the ISSUE 8
acceptance workload: requests sharing a long system prompt at a fixed
pool budget must admit >= 2x the unshared paged concurrency via
copy-on-write page adoption, and a warm greedy replay with
``speculative_k=4`` (donor-stream drafts) must decode >= 1.5x the
tok/s of the per-token fused decode path — token streams identical to
the unshared, non-speculative engine throughout.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, note
from repro.configs import RunConfig, get_config
from repro.data import SyntheticStream
from repro.models import init_model
from repro.serve import Request, ServeEngine


def _measure(fn, warmup: int = 1, iters: int = 3):
    """(median wall seconds, last result) — serving loops are host-driven,
    so the wall clock (not device timings) is the quantity of interest."""
    out = None
    for _ in range(warmup):
        out = fn()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2], out


def run(budget: str = "small"):
    arch = "internlm2-1.8b_smoke" if budget == "small" else "llama-60m"
    B, lp, gen = (4, 32, 32) if budget == "small" else (8, 64, 64)
    cfg = get_config(arch)
    rcfg = RunConfig(compute_dtype="float32", param_dtype="float32",
                     policy_name="none")
    params, _ = init_model(cfg, rcfg, jax.random.key(0))
    stream = SyntheticStream.for_arch(cfg, lp, B)
    batch = {k: jnp.asarray(v) for k, v in stream.get_batch(0).items()
             if k in ("tokens", "image_embeds")}
    max_len = lp + gen + 1

    # ---- fused scan vs per-token loop: DECODE only, prefill outside the
    # timed region on both sides, jit caches reused (steady state) --------
    from repro.models import decode_step as _decode_step
    from repro.models import prefill as _prefill

    prefill_fn = jax.jit(lambda p, b: _prefill(cfg, rcfg, p, b, max_len))
    step_fn = jax.jit(lambda p, t, pos, c: _decode_step(cfg, rcfg, p, t, pos, c))
    logits0, caches0 = prefill_fn(params, batch)
    tok0 = jnp.argmax(logits0[:, -1, : cfg.vocab_size], axis=-1
                      ).astype(jnp.int32)[:, None]
    n_steps = gen - 1  # token 0 comes from prefill logits on both paths

    def per_token_decode(stream: bool):
        """The seed greedy loop. ``stream=False`` is that loop verbatim:
        tokens stay on device, so dispatch overlaps compute — but nothing
        can be streamed out and no stop condition can be checked.
        ``stream=True`` materializes each token on the host, which is what
        per-token *serving* (emit + eos check every step, like the engine
        does) actually costs."""
        tok, caches, out = tok0, caches0, [tok0]
        for i in range(n_steps):
            pos = jnp.full((B, 1), lp + i, jnp.int32)
            logits, caches = step_fn(params, tok, pos, caches)
            tok = jnp.argmax(logits[:, :, : cfg.vocab_size], axis=-1
                             ).astype(jnp.int32)
            if stream:
                tok = jnp.asarray(np.asarray(tok))
            out.append(tok)
        return jnp.concatenate(out, axis=1)

    eng_fused = ServeEngine(cfg, rcfg, params, max_slots=B, max_len=max_len,
                            decode_block=n_steps)
    same_reqs = lambda: [Request(uid=i,
                                 tokens=np.asarray(batch["tokens"][i]).tolist(),
                                 max_new_tokens=gen) for i in range(B)]

    def fused_decode():
        """engine pass; returns (tokens, decode-only seconds)."""
        eng_fused.reset_stats()
        res = eng_fused.run(same_reqs())
        return (np.stack([res[i].tokens for i in range(B)]),
                eng_fused.stats()["decode_s"])

    toks_fused, _ = fused_decode()
    toks_loop = np.asarray(per_token_decode(stream=True))
    assert (toks_fused == toks_loop).all(), "fused scan diverged from loop"

    s_stream, _ = _measure(
        lambda: jax.block_until_ready(per_token_decode(stream=True)))
    s_async, _ = _measure(
        lambda: jax.block_until_ready(per_token_decode(stream=False)))
    fused_times = sorted(fused_decode()[1] for _ in range(3))
    s_fused = fused_times[1]
    tps_stream = B * n_steps / s_stream
    tps_async = B * n_steps / s_async
    tps_fused = B * n_steps / s_fused
    emit("serving_decode_per_token_stream", s_stream * 1e6,
         f"tok_per_s={tps_stream:.1f}")
    emit("serving_decode_per_token_async", s_async * 1e6,
         f"tok_per_s={tps_async:.1f}")
    emit("serving_decode_fused_scan", s_fused * 1e6,
         f"tok_per_s={tps_fused:.1f}")
    emit("serving_fused_speedup_x", tps_fused / tps_stream,
         "acceptance: >= 3x over the per-token serving loop "
         f"(vs async-no-stream loop: {tps_fused / tps_async:.1f}x)")

    # ---- engine with staggered admissions: prefill rate + latency tails --
    eng = ServeEngine(cfg, rcfg, params, max_slots=B, max_len=max_len,
                      decode_block=8)

    def engine_pass():
        reqs = [Request(uid=i,
                        tokens=np.asarray(batch["tokens"][i]).tolist()[
                            : max(4, lp - 2 * (i % 3))],
                        max_new_tokens=gen)
                for i in range(B)]
        eng.run(reqs)

    engine_pass()  # compile every (prompt-length, decode-block) variant
    eng.reset_stats()
    engine_pass()
    st = eng.stats()
    emit("serving_prefill", st["prefill_s"] * 1e6,
         f"tok_per_s={st['prefill_tok_s']:.1f}")
    emit("serving_engine_decode", st["decode_s"] * 1e6,
         f"tok_per_s={st['decode_tok_s']:.1f}")
    emit("serving_queue_wait_p90_us", st["queue_wait_ms_p90"] * 1e3, "")
    emit("serving_ttft_p50_us", st["ttft_ms_p50"] * 1e3, "")
    emit("serving_ttft_p90_us", st["ttft_ms_p90"] * 1e3, "")
    note(f"[serving] {arch} B={B} prompt={lp} gen={gen}: fused "
         f"{tps_fused:.0f} tok/s vs per-token streaming {tps_stream:.0f} "
         f"(async {tps_async:.0f}) tok/s ({tps_fused / tps_stream:.1f}x); "
         f"engine ttft p50/p90 "
         f"{st['ttft_ms_p50']:.2f}/{st['ttft_ms_p90']:.2f} ms")


def run_paged_mixed(budget: str = "small"):
    """Mixed-length workload at a fixed KV pool size: how many requests
    can each cache layout actually keep in flight, and what does it
    reserve to do so?

    The dense engine must carve the budget into whole ``max_len`` slabs,
    so its concurrency is ``pool_tokens // max_len`` regardless of the
    actual prompt mix. The paged engine reserves
    ``ceil((prompt + gen) / page_size)`` pages per request, so short
    requests stop paying for long ones. Acceptance: >= 2x admissible
    concurrency (equivalently >= 2x lower reserved bytes per in-flight
    request) on the skewed-short mix below.
    """
    arch = "internlm2-1.8b_smoke" if budget == "small" else "llama-60m"
    if budget == "small":
        lengths = [8, 8, 12, 16, 16, 24, 8, 32, 48, 12, 64, 96,
                   8, 16, 24, 8, 12, 32, 16, 8]
        gen, page, max_len, pool_tokens, paged_slots = 12, 16, 128, 512, 12
    else:
        lengths = [32, 48, 64, 96, 128, 192, 256, 384, 512, 768, 1024,
                   1536, 2048, 64, 128, 256, 32, 96, 512, 48]
        gen, page, max_len, pool_tokens, paged_slots = 64, 64, 2176, 8704, 16
    cfg = get_config(arch)
    rcfg = RunConfig(compute_dtype="float32", param_dtype="float32",
                     policy_name="none")
    params, _ = init_model(cfg, rcfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=l).tolist()
               for l in lengths]
    mk = lambda: [Request(uid=i, tokens=prompts[i], max_new_tokens=gen)
                  for i in range(len(prompts))]

    # dense at the same budget: whole max_len slabs, so the pool fits
    # exactly pool_tokens // max_len of them
    dense_slots = max(1, pool_tokens // max_len)
    eng_d = ServeEngine(cfg, rcfg, params, max_slots=dense_slots,
                        max_len=max_len, decode_block=8)
    out_d = eng_d.run(mk())

    eng_p = ServeEngine(cfg, rcfg, params, max_slots=paged_slots,
                        max_len=max_len, decode_block=8,
                        cache_layout="paged", page_size=page,
                        pool_tokens=pool_tokens)
    out_p = eng_p.run(mk())
    for i in range(len(prompts)):
        assert out_p[i].tokens == out_d[i].tokens, \
            f"paged diverged from dense on request {i}"

    st_d, st_p = eng_d.stats(), eng_p.stats()
    conc_d, conc_p = st_d["peak_active"], st_p["peak_active"]
    res_d, res_p = (st_d["peak_kv_reserved_bytes"],
                    st_p["peak_kv_reserved_bytes"])
    per_req_d = res_d / max(1, conc_d)
    per_req_p = res_p / max(1, conc_p)
    emit("serving_paged_mixed_concurrency_dense", conc_d,
         f"pool={pool_tokens}tok max_len={max_len}")
    emit("serving_paged_mixed_concurrency_paged", conc_p,
         f"pool={pool_tokens}tok page={page}")
    emit("serving_paged_mixed_concurrency_ratio", conc_p / max(1, conc_d),
         "acceptance: >= 2x admissible concurrent requests")
    emit("serving_paged_mixed_reserved_mb_dense", res_d / 1e6,
         f"per_inflight_req_mb={per_req_d / 1e6:.3f}")
    emit("serving_paged_mixed_reserved_mb_paged", res_p / 1e6,
         f"per_inflight_req_mb={per_req_p / 1e6:.3f}")
    emit("serving_paged_mixed_reserved_per_req_ratio",
         per_req_d / max(1.0, per_req_p),
         "dense/paged reserved bytes per in-flight request")
    note(f"[serving-paged] {arch} {len(prompts)} reqs "
         f"lens {min(lengths)}-{max(lengths)} gen={gen} "
         f"pool={pool_tokens} tok: concurrency {conc_p} paged vs {conc_d} "
         f"dense ({conc_p / max(1, conc_d):.1f}x); reserved/req "
         f"{per_req_p / 1e6:.3f} vs {per_req_d / 1e6:.3f} MB "
         f"({per_req_d / max(1.0, per_req_p):.1f}x); tokens identical")


def run_paged_kvquant(budget: str = "small"):
    """run_paged_mixed's workload with compressed KV pools at the SAME
    fixed pool byte budget: ``cache.kv=int8`` stores ~3.2x fewer bytes
    per token (fp32 smoke dims), so the allocator mints proportionally
    more pages and admission keeps more requests in flight. Acceptance:
    >= 1.8x peak concurrency over the fp paged baseline. int4/svd rows
    are reported alongside (more compression, lossier logits)."""
    arch = "internlm2-1.8b_smoke" if budget == "small" else "llama-60m"
    if budget == "small":
        lengths = [8, 8, 12, 16, 16, 24, 8, 32, 48, 12, 64, 96,
                   8, 16, 24, 8, 12, 32, 16, 8]
        gen, page, max_len, pool_tokens, slots = 12, 16, 128, 384, 20
    else:
        lengths = [32, 48, 64, 96, 128, 192, 256, 384, 512, 768, 1024,
                   1536, 2048, 64, 128, 256, 32, 96, 512, 48]
        gen, page, max_len, pool_tokens, slots = 64, 64, 2176, 8704, 20
    cfg = get_config(arch)
    rcfg = RunConfig(compute_dtype="float32", param_dtype="float32",
                     policy_name="none")
    params, _ = init_model(cfg, rcfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=l).tolist()
               for l in lengths]
    mk = lambda: [Request(uid=i, tokens=prompts[i], max_new_tokens=gen)
                  for i in range(len(prompts))]

    def paged_engine(spec: str):
        eng = ServeEngine(cfg, rcfg, params, max_slots=slots,
                          max_len=max_len, decode_block=8,
                          cache_layout="paged", page_size=page,
                          pool_tokens=pool_tokens, cache_compress=spec)
        out = eng.run(mk())
        return eng, out

    eng_fp, out_fp = paged_engine("")
    st_fp = eng_fp.stats()
    base_conc = max(1, st_fp["peak_active"])
    emit("serving_kvquant_concurrency_fp", st_fp["peak_active"],
         f"pool={pool_tokens}tok page={page} (fp32 paged baseline)")
    ratio_int8 = 0.0
    for spec in ("int8", "int4", "svd(r=1/4)"):
        eng, out = paged_engine(spec)
        st = eng.stats()
        pools = st["cache_pools"]
        tb = sum(p["token_bytes"] for p in pools.values())
        same = sum(out[i].tokens == out_fp[i].tokens
                   for i in range(len(prompts)))
        key = spec.split("(")[0]
        if key == "int8":
            ratio_int8 = st["peak_active"] / base_conc
        emit(f"serving_kvquant_concurrency_{key}", st["peak_active"],
             f"compression_x={st['cache/kv_compression_x']:.2f} "
             f"bytes_per_token={tb} "
             f"greedy_match={same}/{len(prompts)}")
        emit(f"serving_kvquant_concurrency_ratio_{key}",
             st["peak_active"] / base_conc,
             "acceptance: int8 >= 1.8x fp paged concurrency at the "
             "same pool byte budget" if key == "int8" else
             "reported alongside (lossier formats)")
        note(f"[serving-kvquant] {arch} cache.kv={spec}: peak "
             f"concurrency {st['peak_active']} vs {base_conc} fp "
             f"({st['peak_active'] / base_conc:.1f}x), "
             f"x{st['cache/kv_compression_x']:.2f} bytes/token, "
             f"greedy match {same}/{len(prompts)}")
    assert ratio_int8 >= 1.8, \
        f"int8 concurrency ratio {ratio_int8:.2f} < 1.8x acceptance"


def run_disagg(budget: str = "small"):
    """Disaggregated serving microbenchmark (``serving_disagg``).

    Per-stage costs of the JetStream-shaped API — prefill tok/s, insert
    latency (page reservation + slot splice), generate tok/s — then the
    scaling claim: a Router over N decode replicas at a FIXED per-replica
    pool budget admits ~N x the aggregate concurrency of one replica.
    Acceptance: >= 3x aggregate admissible concurrency from 1 -> 4
    replicas, with routed token streams identical to the solo engine.
    """
    from repro.serve import Router

    arch = "internlm2-1.8b_smoke" if budget == "small" else "llama-60m"
    if budget == "small":
        lengths = [8, 10, 12, 8, 14, 10, 12, 8, 10, 12, 14, 8]
        gen, page, max_len, pool_tokens, slots = 12, 8, 64, 32, 2
    else:
        lengths = [64, 96, 128, 64, 192, 96, 128, 64, 96, 128, 192, 64]
        gen, page, max_len, pool_tokens, slots = 64, 64, 512, 256, 2
    cfg = get_config(arch)
    rcfg = RunConfig(compute_dtype="float32", param_dtype="float32",
                     policy_name="none")
    params, _ = init_model(cfg, rcfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=l).tolist()
               for l in lengths]
    mk = lambda: [Request(uid=i, tokens=prompts[i], max_new_tokens=gen)
                  for i in range(len(prompts))]
    eng_kw = dict(max_slots=slots, max_len=max_len, decode_block=8,
                  cache_layout="paged", page_size=page,
                  pool_tokens=pool_tokens)

    # ---- per-stage costs on one engine (warm pass first: compiles) ------
    solo = ServeEngine(cfg, rcfg, params, **eng_kw)
    out_solo = solo.run(mk())
    solo.reset_stats()
    out_solo = solo.run(mk())
    st = solo.stats()
    emit("serving_disagg_prefill_tok_s", st["prefill_tok_s"],
         f"batch-1 prompt stage, {st['prefill_compiles']} bucket compiles")
    emit("serving_disagg_insert_ms", st["insert_ms_avg"],
         f"page reservation + slot splice, {st['insert_count']} inserts")
    emit("serving_disagg_generate_tok_s", st["decode_tok_s"],
         "fused decode blocks across all slots")

    # ---- replica scaling at fixed per-replica pool budget ---------------
    def routed(n: int):
        router = Router([ServeEngine(cfg, rcfg, params, **eng_kw)
                         for _ in range(n)])
        out = router.run(mk())
        return router, out

    peaks = {}
    for n in (1, 2, 4):
        router, out = routed(n)
        for i in range(len(prompts)):
            assert out[i].tokens == out_solo[i].tokens, \
                f"replicas={n}: request {i} diverged from solo"
        peaks[n] = router.peak_active
        emit(f"serving_disagg_concurrency_{n}replica", peaks[n],
             f"pool={pool_tokens}tok/replica, {len(prompts)} reqs, "
             f"replicas used: {len(set(router.placement.values()))}")
    scaling = peaks[4] / max(1, peaks[1])
    emit("serving_disagg_scaling_1_to_4", scaling,
         "acceptance: >= 3x aggregate admissible concurrency at fixed "
         "per-replica pool budget")
    note(f"[serving-disagg] {arch} {len(prompts)} reqs gen={gen} "
         f"pool={pool_tokens}tok/replica: aggregate concurrency "
         f"{peaks[1]} -> {peaks[2]} -> {peaks[4]} for 1 -> 2 -> 4 "
         f"replicas ({scaling:.1f}x); prefill {st['prefill_tok_s']:.0f} "
         f"tok/s, insert {st['insert_ms_avg']:.1f} ms, generate "
         f"{st['decode_tok_s']:.0f} tok/s; routed tokens == solo")
    assert scaling >= 3.0, \
        f"replica scaling {scaling:.2f} < 3x acceptance (1 -> 4 replicas)"


def run_prefix_shared(budget: str = "small"):
    """Copy-on-write prefix sharing + speculative verify acceptance
    (``serving_prefix_shared``).

    Part 1 — capacity: 16 requests sharing one long system prompt
    against a pool sized for ~4 unshared reservations. The unshared
    engine re-reserves the full prompt per request; prefix sharing
    adopts the system prompt's full pages (one refcounted physical copy)
    and COW-splits only the divergent partial page, so admission charges
    just the per-request tail. Acceptance: >= 2x peak admissible
    concurrency, token streams identical to the unshared engine.

    Part 2 — latency: a warm replay of the same prompts with
    ``speculative_k=4``. Retired prefixes seed donor streams, so drafts
    come from the previous generation and verify in ONE fused (k+1)-row
    call through the paged flash-decode path — ~5 emitted tokens per
    model call vs 1 for the per-token fused decode baseline.
    Acceptance: >= 1.5x decode tok/s, tokens identical.
    """
    arch = "internlm2-1.8b_smoke" if budget == "small" else "llama-60m"
    if budget == "small":
        n_req, system_len, gen, page = 16, 260, 12, 16
    else:
        n_req, system_len, gen, page = 16, 516, 32, 32
    # system_len is deliberately NOT page-aligned: followers diverge
    # mid-page, so every admission after the first exercises the COW
    # split. Tails are > page so each prompt also owns distinct full
    # pages — the replay can then match its OWN retired prefix end-to-
    # end and draft from the donor stream.
    tail = lambda i: page + i % 3
    max_len = system_len + 2 * page + gen
    per_req_pages = -(-(system_len + tail(2) + gen) // page)
    pool_tokens = 4 * (per_req_pages + 1) * page
    cfg = get_config(arch)
    rcfg = RunConfig(compute_dtype="float32", param_dtype="float32",
                     policy_name="none")
    params, _ = init_model(cfg, rcfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    system = rng.integers(0, cfg.vocab_size, size=system_len).tolist()
    prompts = [system + rng.integers(0, cfg.vocab_size,
                                     size=tail(i)).tolist()
               for i in range(n_req)]
    mk = lambda off: [Request(uid=off + i, tokens=prompts[i],
                              max_new_tokens=gen) for i in range(n_req)]
    kw = dict(max_slots=n_req, max_len=max_len, decode_block=1,
              cache_layout="paged", page_size=page,
              pool_tokens=pool_tokens, prefix_cache=n_req)

    # ---- capacity at a fixed pool budget --------------------------------
    eng_b = ServeEngine(cfg, rcfg, params, **kw)
    out_b = eng_b.run(mk(0))
    eng_s = ServeEngine(cfg, rcfg, params, prefix_share=True, **kw)
    out_s = eng_s.run(mk(0))
    for i in range(n_req):
        assert out_s[i].tokens == out_b[i].tokens, \
            f"prefix sharing diverged on request {i}"
    st_b, st_s = eng_b.stats(), eng_s.stats()
    conc_b, conc_s = st_b["peak_active"], st_s["peak_active"]
    emit("serving_prefix_shared_concurrency_unshared", conc_b,
         f"pool={pool_tokens}tok page={page} system={system_len}")
    emit("serving_prefix_shared_concurrency_shared", conc_s,
         f"hits={st_s['prefix_hits']} pages_adopted="
         f"{st_s['prefix_pages_adopted']} cow_splits="
         f"{st_s['cow_page_splits']}")
    conc_ratio = conc_s / max(1, conc_b)
    emit("serving_prefix_shared_concurrency_ratio", conc_ratio,
         "acceptance: >= 2x admissible concurrency at fixed pool budget")

    # ---- speculative replay decode throughput ---------------------------
    eng_k = ServeEngine(cfg, rcfg, params, prefix_share=True,
                        speculative_k=4, **kw)
    eng_k.run(mk(0))              # cold: compile, retire prefixes, seed donors
    eng_k.reset_stats()
    out_r = eng_k.run(mk(1000))   # warm replay: donor-stream drafts
    eng_b.reset_stats()
    out_b2 = eng_b.run(mk(1000))  # warmed per-token fused decode baseline
    for i in range(n_req):
        assert out_r[1000 + i].tokens == out_b[i].tokens, \
            f"speculative replay diverged on request {i}"
        assert out_b2[1000 + i].tokens == out_b[i].tokens
    st_k, st_b2 = eng_k.stats(), eng_b.stats()
    tps_base, tps_spec = st_b2["decode_tok_s"], st_k["decode_tok_s"]
    spec_ratio = tps_spec / max(1e-9, tps_base)
    emit("serving_prefix_shared_decode_per_token", tps_base,
         "per-token fused decode baseline, tok/s")
    emit("serving_prefix_shared_decode_speculative", tps_spec,
         f"k=4 verify calls={st_k['spec_verify_calls']} accept_rate="
         f"{st_k['spec_accept_rate']:.2f}")
    emit("serving_prefix_shared_spec_speedup_x", spec_ratio,
         "acceptance: >= 1.5x decode tok/s on the warm greedy replay")
    note(f"[serving-prefix-shared] {arch} {n_req} reqs sharing "
         f"{system_len}-token system prompt, pool={pool_tokens} tok: "
         f"concurrency {conc_s} shared vs {conc_b} unshared "
         f"({conc_ratio:.1f}x), {st_s['cow_page_splits']} cow splits; "
         f"replay decode {tps_spec:.0f} tok/s spec(k=4) vs "
         f"{tps_base:.0f} per-token ({spec_ratio:.1f}x, accept rate "
         f"{st_k['spec_accept_rate']:.2f}); tokens identical")
    assert conc_ratio >= 2.0, \
        f"shared concurrency ratio {conc_ratio:.2f} < 2x acceptance"
    assert spec_ratio >= 1.5, \
        f"speculative replay speedup {spec_ratio:.2f} < 1.5x acceptance"
    assert st_k["spec_accept_rate"] >= 0.5, \
        f"donor drafting regressed: accept rate {st_k['spec_accept_rate']:.2f}"


if __name__ == "__main__":
    run()
    run_paged_mixed()
    run_paged_kvquant()
    run_disagg()
    run_prefix_shared()
