"""Continuous-batching serving driver.

  PYTHONPATH=src python -m repro.launch.serve --arch llama-60m --batch 4 \
      --requests 8 --prompt-len 64 --gen 32 --temperature 0.8 --top-k 40

Requests get staggered prompt lengths so admissions and evictions overlap
mid-stream (the continuous-batching path, not one static batch). ``--smoke``
runs the workload twice and asserts identical outputs and tok/s > 0 — the
CI serving smoke job.

``--replicas N`` serves through the disaggregated front instead of one
engine: a serve.Router over N decode replicas (each with ``--batch``
slots and its own page pools) with page-aware least-loaded admission;
``--dedicated-prefill`` adds a separate prefill engine whose Prefixes
cross to the decode replicas in host form. ``--mesh-data D`` runs ONE
engine with its pools sharded into D per-replica shards on a device
mesh (the other scaling axis; needs D devices — pair with
XLA_FLAGS=--xla_force_host_platform_device_count=D on CPU).
"""
from __future__ import annotations

import argparse
import sys

import jax
import numpy as np

from repro.configs import RunConfig, get_config
from repro.data import SyntheticStream
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_model
from repro.serve import Request, SamplingParams, ServeEngine


def _build_requests(cfg, args) -> list[Request]:
    stream = SyntheticStream.for_arch(cfg, args.prompt_len, args.requests)
    batch = stream.get_batch(0)
    requests = []
    for i in range(args.requests):
        # stagger prompt lengths so requests join/leave mid-stream
        lp = max(4, args.prompt_len - 3 * (i % 4))
        img = batch["image_embeds"][i] if cfg.vision_tokens else None
        requests.append(Request(
            uid=i,
            tokens=np.asarray(batch["tokens"][i][:lp]).tolist(),
            max_new_tokens=args.gen,
            sampling=SamplingParams(temperature=args.temperature,
                                    top_k=args.top_k, seed=args.seed + i),
            image_embeds=img,
        ))
    return requests


def _make_engine(cfg, rcfg, params, args, *, mesh=None, slots=None):
    return ServeEngine(cfg, rcfg, params, max_slots=slots or args.batch,
                       max_len=args.prompt_len + args.gen + 1,
                       decode_block=args.decode_block,
                       cache_layout=args.cache_layout,
                       page_size=args.page_size,
                       pool_tokens=args.pool_tokens or None,
                       cache_compress=args.cache_compress,
                       prefix_share=args.prefix_share,
                       speculative_k=args.speculative_k,
                       mesh=mesh)


def _serve_once(cfg, rcfg, params, args):
    mesh = None
    if args.mesh_data > 1:
        from repro.launch.mesh import make_debug_mesh

        mesh = make_debug_mesh(args.mesh_data, 1)
    if args.replicas > 1:
        from repro.serve import Router

        replicas = [_make_engine(cfg, rcfg, params, args)
                    for _ in range(args.replicas)]
        pf = (_make_engine(cfg, rcfg, params, args, slots=1)
              if args.dedicated_prefill else None)
        router = Router(replicas, prefill_engine=pf)
        results = router.run(_build_requests(cfg, args))
        return results, router.stats()
    engine = _make_engine(cfg, rcfg, params, args, mesh=mesh)
    results = engine.run(_build_requests(cfg, args))
    return results, engine.stats()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4, help="engine slots")
    ap.add_argument("--requests", type=int, default=0,
                    help="total requests (default: 2x batch)")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--decode-block", type=int, default=8,
                    help="decode tokens per fused lax.scan call")
    ap.add_argument("--cache-layout", default="dense",
                    choices=["dense", "paged"],
                    help="decode KV cache: dense per-slot slabs, or paged "
                         "pools + block tables (DESIGN.md §9)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged layout)")
    ap.add_argument("--pool-tokens", type=int, default=0,
                    help="KV pool budget in tokens per pool "
                         "(0 = dense-equivalent worst case)")
    ap.add_argument("--cache-compress", default="",
                    help="cache-side CompressionPlan spec for the paged "
                         "KV pools: 'int8', 'int4(group=64)', "
                         "'svd(r=1/4)' or full 'cache.kv=...' rule form "
                         "(requires --cache-layout paged; DESIGN.md §9)")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--compression", default="",
                    help="CompressionPlan spec exercised during prefill, "
                         "e.g. 'attn.qkv=pamm(r=1/512)' (DESIGN.md §2)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--top-k", type=int, default=0, help="0 = full vocab")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--replicas", type=int, default=1,
                    help="decode replicas behind a serve.Router (each gets "
                         "--batch slots and its own page pools)")
    ap.add_argument("--dedicated-prefill", action="store_true",
                    help="with --replicas: prefill on a separate engine and "
                         "hand Prefixes to decode replicas in host form")
    ap.add_argument("--mesh-data", type=int, default=1,
                    help="shard one engine's slots/pools into this many "
                         "per-replica shards on a device mesh (needs that "
                         "many devices)")
    ap.add_argument("--prefix-share", action="store_true",
                    help="copy-on-write prefix sharing: requests whose "
                         "prompts share full KV pages with a live or "
                         "recently-retired request adopt those pages "
                         "instead of re-reserving them (paged layout, "
                         "single replica; DESIGN.md §9)")
    ap.add_argument("--speculative-k", type=int, default=0,
                    help="self-speculative decode: draft k tokens on the "
                         "host and verify them in one fused multi-row "
                         "decode call (paged layout, greedy sampling)")
    ap.add_argument("--smoke", action="store_true",
                    help="run twice, assert determinism and tok/s > 0")
    args = ap.parse_args(argv)
    if not args.requests:
        args.requests = 2 * args.batch

    cfg = get_config(args.arch)
    rcfg = RunConfig(compute_dtype=args.dtype, param_dtype=args.dtype,
                     policy_name="none", compression=args.compression)
    params, _ = init_model(cfg, rcfg, jax.random.key(0))

    results, stats = _serve_once(cfg, rcfg, params, args)
    for uid in sorted(results):
        r = results[uid]
        print(f"req {uid}: prompt={r.prompt_len} new={len(r.tokens)} "
              f"finish={r.finish_reason} {r.decode_tok_s:.1f} tok/s "
              f"sample={r.tokens[:8]}")
    if args.replicas > 1:
        print(f"router: {stats['replicas']} replicas"
              + (" + dedicated prefill" if stats["dedicated_prefill"]
                 else "")
              + f" | prefill {stats['prefill_tok_s']:.1f} tok/s | "
              f"decode {stats['decode_tok_s']:.1f} tok/s | "
              f"peak aggregate concurrency {stats['peak_active_aggregate']}"
              f" | peak reserved "
              f"{stats['peak_kv_reserved_bytes'] / 2**20:.2f} MB")
    else:
        print(f"prefill {stats['prefill_tok_s']:.1f} tok/s | "
              f"decode {stats['decode_tok_s']:.1f} tok/s | "
              f"p50 {stats['p50_token_latency_ms']:.2f} ms | "
              f"p95 {stats['p95_token_latency_ms']:.2f} ms | "
              f"cache {stats['cache_slot_bytes'] / 1e6:.2f} MB/slot")
        layout = args.cache_layout + (
            f"+{args.cache_compress}" if args.cache_compress else "")
        print(f"[{layout}] kv capacity "
              f"{stats['cache/kv_capacity_mb']:.2f} MB | peak reserved "
              f"{stats['peak_kv_reserved_bytes'] / 2**20:.2f} MB | peak used "
              f"{stats['peak_kv_used_bytes'] / 2**20:.2f} MB | "
              f"peak concurrency {stats['peak_active']} | "
              f"replica shards {stats['replica_shards']} | "
              f"compression x{stats['cache/kv_compression_x']:.2f} | "
              f"{stats['prefill_compiles']} prefill compiles")
        if args.prefix_share:
            print(f"[prefix-share] hits {stats['prefix_hits']} | pages "
                  f"adopted {stats['prefix_pages_adopted']} | cow splits "
                  f"{stats['cow_page_splits']} | retired prefixes kept "
                  f"{stats['retired_prefixes']}")
        if args.speculative_k:
            print(f"[speculative k={args.speculative_k}] verify calls "
                  f"{stats['spec_verify_calls']} | drafted "
                  f"{stats['spec_tokens_drafted']} | accepted "
                  f"{stats['spec_tokens_accepted']} | accept rate "
                  f"{stats['spec_accept_rate']:.2f}")

    if args.smoke:
        again, stats2 = _serve_once(cfg, rcfg, params, args)
        same = all(again[u].tokens == results[u].tokens for u in results)
        if not same:
            print("SMOKE FAIL: outputs not deterministic", file=sys.stderr)
            sys.exit(1)
        if not (stats["decode_tok_s"] > 0 and stats["prefill_tok_s"] > 0):
            print("SMOKE FAIL: zero throughput", file=sys.stderr)
            sys.exit(1)
        print("SMOKE OK")


if __name__ == "__main__":
    enable_compile_cache()
    main()
