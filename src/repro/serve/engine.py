"""Continuous-batching serving engine over the Pallas attention path.

The engine is built JetStream-shaped: three explicit stages —

  prefill(params, request)            -> Prefix
  insert(prefix, decode_state, slot)  -> DecodeState
  generate(params, decode_state)      -> (DecodeState, GenerateOutput)

— with the classic ``submit``/``step`` continuous-batching loop rebuilt
as a thin orchestrator on top (admission = prefill + insert; the fused
decode block = generate; per-request bookkeeping stays host-side in the
orchestrator). A :class:`Prefix` is the transferable product of prefill:
the batch-1 cache tree plus the first sampled token and the request's
sampling state — ``to_host()`` converts its device leaves to numpy so a
router can hand it from a prefill engine to a different decode replica
(serve/router.py fronts N of them).

A request's lifecycle through the orchestrator:

  QUEUED   -> in the FIFO admission queue
  PREFILL  -> admitted to a free slot: the prompt runs alone (batch 1)
              through ``models.prefill`` — attention via the Pallas
              FlashAttention kernel on TPU (``RunConfig.attn_kernel``) —
              producing a Prefix; ``insert`` splices its caches into the
              slot (serve/cache.py). The first token was sampled from
              the prefill logits.
  DECODE   -> the slot joins the fused decode loop: ``decode_block``
              tokens per jitted ``lax.scan`` call over the whole batch,
              single-query flash attention against the slot caches
              (kernels/flash_decode.py), per-slot sampling and stop
              conditions evaluated inside the scan.
  FINISHED -> eos / max_new_tokens / max_len reached; the slot frees with
              no cache reset — a parked position (-1) makes the slot's
              decode step inert, and the next admission overwrites it.

Per-sequence math is row-independent end to end, so a request's tokens
are identical whether it runs alone or continuously batched (pinned by
tests/test_serving.py). Known exception: MoE token-dropping couples rows
through expert capacity, so batch composition can perturb MoE outputs —
serve MoE archs with ``capacity_factor`` high enough to avoid drops if
exact parity matters.

The scheduler deliberately keeps admission OUT of the fused loop: a scan
over decode steps never re-enters Python, and the engine only pays the
(batch-1) prefill + slot-splice when the queue is non-empty.

Cache layouts (``cache_layout=dense|paged``): ``dense`` reserves a
slot-contiguous ``(layers, B, max_len, KV, dh)`` slab per slot — a short
prompt pays for ``max_len`` whether it uses it or not. ``paged`` backs
the self-attention caches with page pools + per-slot block tables
(serve/paging.py, models/attention.PagedKVCache): admission reserves
``ceil((prompt + max_new) / page_size)`` pages per pool, the predicate
becomes *free slot AND enough free pages in every pool*, and eviction
returns the pages to the host free list with zero device work (the same
parked-position trick — no live block table maps a freed page, and
``page_pos`` resets when the page is re-issued). Both layouts are
token-identical (tests/test_paging.py pins paged == dense == solo).

On a mesh, the paged pools shard PER REPLICA: serve/cache.shard_slots
reshapes every pool into ``dp`` equal shards (shard-local page ids,
slot chunk [s*B/dp, (s+1)*B/dp) per shard), the engine keeps one
PageAllocator per pool PER SHARD, and admission becomes page-aware
replica placement — a free slot on a replica whose every pool has room.
Decode stays shard-local (kernels/flash_decode sharded dispatchers), so
tokens match the single-host engine exactly (tests/test_multidevice.py).

Prompt-length bucketing: admission pads prompts up to a power-of-two
bucket so ``prefill`` compiles once per bucket instead of once per
distinct prompt length. Pad rows are masked out of the cache splice and
the first-token logits are read at the true last-prompt position.
Bucketing auto-disables (with a one-time warning naming the arch) for
archs with sequence-coupled prefill state (rec/ssm recurrences, MoE
capacity), where extra pad tokens would perturb the spliced state.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time
import warnings
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import stats as stats_lib
from repro.core.plan import cache_plan_from_spec
from repro.models import decode_step, init_caches, prefill
from repro.models.attention import PAGED_CACHE_TYPES, SVDPagedKVCache
from repro.runtime.spans import span
from repro.serve import cache as cache_lib
from repro.serve import paging
from repro.serve.sampling import SamplingParams, sample_tokens

PAD_TOKEN = -1

# archs already warned about prefill-bucket auto-disable (one warning per
# arch per process, not one per engine — engines churn in tests/benches)
_BUCKET_WARNED: set[str] = set()


def _percentile(sorted_samples, p: float) -> float:
    """Nearest-rank percentile of an ascending-sorted list: the smallest
    sample with at least ``p`` of the mass at or below it, i.e. index
    ``ceil(p * n) - 1``. (``int(p * n)`` overshoots: p50 of two samples
    would return the max.)"""
    n = len(sorted_samples)
    if n == 0:
        return 0.0
    rank = max(1, math.ceil(p * n))
    return sorted_samples[min(n - 1, rank - 1)]


@dataclasses.dataclass
class Request:
    """One generation request. ``eos_id`` < 0 disables the eos stop."""

    uid: int
    tokens: Sequence[int]
    max_new_tokens: int
    sampling: SamplingParams = SamplingParams()
    eos_id: int = -1
    image_embeds: Optional[np.ndarray] = None  # (vision_tokens, d) for vlm


@dataclasses.dataclass
class RequestOutput:
    """A finished request. ``queued_s`` runs from ``submit`` to the start of
    its admission; ``ttft_s`` from ``submit`` to the return of the
    ``step`` that admitted it, when its first token reaches the caller.
    Both are None for a request admitted through ``admit_prefix`` (a
    router's hand-off), which never passed this engine's queue."""

    uid: int
    prompt_len: int
    tokens: list[int]
    finish_reason: str          # "eos" | "length"
    prefill_s: float
    queued_s: float | None = None
    ttft_s: float | None = None


@dataclasses.dataclass
class Prefix:
    """The transferable product of the prefill stage (JetStream shape).

    Holds everything ``insert`` needs to light up a decode slot: the
    batch-1 prefill cache tree, the first sampled token, and the request
    (sampling params ride with it). ``caches`` leaves live on the prefill
    engine's devices; :meth:`to_host` converts them to numpy so the
    Prefix can cross an engine boundary (router prefill->decode handoff —
    in a multi-host deployment this is the wire format).

    A Prefix is single-use: ``insert`` marks it consumed, and a second
    insert raises with the target slot's lifecycle state (stale-handoff
    bugs fail loudly instead of silently double-serving a request).
    """

    uid: int
    request: Request
    prompt_len: int
    first_token: int
    caches: Any                  # batch-1 cache tree (device or numpy)
    prefill_s: float
    consumed: bool = False
    inserted_slot: int | None = None

    def to_host(self) -> "Prefix":
        """Convert cache leaves to numpy in place (transferable form)."""
        self.caches = jax.tree.map(np.asarray, self.caches)
        return self


@dataclasses.dataclass
class DecodeState:
    """Per-slot decode state: the batched cache tree plus the host-side
    slot vectors the fused loop carries. ``insert`` writes one slot;
    ``generate`` advances every active slot one decode block."""

    caches: Any
    slot_uid: np.ndarray      # (B,) int64 request uid; -1 = free
    tok: np.ndarray           # (B,) int32 last sampled token
    pos: np.ndarray           # (B,) int32 next decode position; -1 parked
    remaining: np.ndarray     # (B,) int32 generation budget left
    gen_idx: np.ndarray       # (B,) int32 per-request sample index
    active: np.ndarray        # (B,) bool
    seeds: np.ndarray         # (B,) int32 sampling
    temps: np.ndarray         # (B,) float32
    topks: np.ndarray         # (B,) int32
    eos_ids: np.ndarray       # (B,) int32; -1 = no eos stop

    @classmethod
    def init(cls, caches, B: int) -> "DecodeState":
        return cls(
            caches=caches,
            slot_uid=np.full((B,), -1, np.int64),
            tok=np.zeros((B,), np.int32),
            pos=np.full((B,), -1, np.int32),
            remaining=np.zeros((B,), np.int32),
            gen_idx=np.zeros((B,), np.int32),
            active=np.zeros((B,), bool),
            seeds=np.zeros((B,), np.int32),
            temps=np.zeros((B,), np.float32),
            topks=np.zeros((B,), np.int32),
            eos_ids=np.full((B,), -1, np.int32),
        )

    def slot_state(self, slot: int) -> str:
        """Human-readable lifecycle state of a slot (error messages)."""
        uid = int(self.slot_uid[slot])
        if uid >= 0:
            return (f"active (serving request uid={uid}, "
                    f"pos={int(self.pos[slot])}, "
                    f"{int(self.remaining[slot])} tokens remaining)")
        return "free (released; position parked at -1)"


@dataclasses.dataclass
class _PrefixEntry:
    """One request in the copy-on-write prefix index.

    ``rows`` holds the request's block-table row per paged pool (numpy,
    flat in cache-tree pool order) — the page ids future requests adopt.
    A LIVE entry's pages are kept by its slot's allocator ownership; a
    RETIRED entry keeps only its prompt pages, via an extra
    ``("prefix", uid)`` allocator reference, and additionally records the
    full token ``stream`` (prompt + generation) as a draft donor for
    speculative decode — a new request with the same prompt will, under
    greedy sampling, reproduce that continuation until its sampling
    params diverge from the donor's."""

    uid: int
    tokens: tuple               # prompt token ids
    rows: list                  # per-pool (nb,) np.int32 block-table rows
    stream: list | None = None  # prompt + generated (set at retirement)
    retired: bool = False


@dataclasses.dataclass
class GenerateOutput:
    """Raw product of one fused decode block (orchestrator bookkeeping
    input): per-step emitted tokens and activity masks, host-side."""

    emitted: np.ndarray       # (steps, B) int32; PAD_TOKEN where inactive
    was_active: np.ndarray    # (steps, B) bool
    steps: int


def _state_prop(name: str):
    """Engine attribute delegating to decode_state (the pre-stage-API
    attribute surface — tests and tools read eng.active / eng.caches)."""
    return property(lambda self: getattr(self.decode_state, name),
                    lambda self, v: setattr(self.decode_state, name, v))


class ServeEngine:
    """Continuous-batching engine. See module docstring for the design."""

    def __init__(self, cfg, rcfg, params, *, max_slots: int, max_len: int,
                 decode_block: int = 8, plan=None, n_kv_eff: int | None = None,
                 mesh=None, cache_layout: str | None = None,
                 page_size: int | None = None, pool_tokens: int | None = None,
                 prefill_buckets: bool | None = None,
                 cache_compress: str | None = None,
                 prefix_share: bool = False, speculative_k: int = 0,
                 prefix_cache: int = 8):
        if cfg.embed_inputs:
            raise NotImplementedError(
                "serving needs a token frontend; embed-input archs "
                "(musicgen) are train/score only")
        if cfg.n_codebooks:
            raise NotImplementedError("multi-codebook decode is not served")
        self.cfg, self.rcfg = cfg, rcfg
        self.max_slots, self.max_len = max_slots, max_len
        self.decode_block = decode_block
        self.plan = plan if plan is not None else (rcfg.compression or None)
        self.mesh = mesh
        self.cache_layout = cache_layout or getattr(rcfg, "cache_layout",
                                                    "dense")
        self.page_size = page_size or getattr(rcfg, "kv_page_size", 64)
        spec = (cache_compress if cache_compress is not None
                else getattr(rcfg, "cache_compress", "") or "")
        self.cache_plan = cache_plan_from_spec(spec).resolve(cfg)
        if self.cache_plan.compressed_cache_sites and \
                self.cache_layout != "paged":
            raise ValueError(
                f"cache_compress={spec!r} compresses the paged page pools; "
                "the dense layout has no compressed storage path — pass "
                "cache_layout='paged' or drop cache_compress")
        if pool_tokens is not None and self.cache_layout != "paged":
            raise ValueError(
                "pool_tokens budgets the paged layout's page pools; the "
                "dense layout always reserves max_slots * max_len slabs — "
                "pass cache_layout='paged' or drop pool_tokens")
        # pool_tokens: HBM budget per KV pool in tokens (None = the dense
        # worst case, max_slots * max_len rounded up to pages — same
        # capability, but reserved bytes still track actual requests)
        pool_pages = (None if pool_tokens is None
                      else -(-pool_tokens // self.page_size))

        # n_kv_eff: KV heads replicated for TP divisibility — the slot
        # caches must match the params' KV dim or write_slot's splice fails
        caches = init_caches(cfg, rcfg, max_slots, max_len,
                             n_kv_eff=n_kv_eff,
                             layout=self.cache_layout,
                             page_size=self.page_size,
                             pool_pages=pool_pages,
                             cache_plan=self.cache_plan)
        if any(isinstance(n, SVDPagedKVCache)
               for n in cache_lib.kv_cache_nodes(caches)):
            # calibration-free bases from the K/V projection spectra
            caches = cache_lib.install_svd_bases(caches, params, cfg)

        if mesh is not None:
            # Data-parallel decode: params replicated, per-sequence state
            # sharded over the data axes — dense slot slabs split on the
            # slot axis; paged pools split into per-replica shards with
            # shard-local page ids (serve/cache.shard_slots). The jitted
            # decode loop partitions shard-locally and tokens come out
            # identical to the single-device engine
            # (tests/test_multidevice.py holds it to that).
            from repro.runtime import sharding as rt_sh

            params = jax.device_put(params, rt_sh.replicated(mesh))
            caches = cache_lib.shard_slots(caches, mesh)
            self.n_replicas = (rt_sh.dp_degree(mesh)
                               if self.cache_layout == "paged" else 1)
        else:
            self.n_replicas = 1
        self.params = params
        self.decode_state = DecodeState.init(caches, max_slots)

        # one host-side allocator per page pool PER REPLICA SHARD, in
        # cache-tree order (the same traversal _alloc_rows uses) —
        # single-host engines have exactly one shard, so self.allocators
        # is one-per-pool there, as before. Dense layout has none and
        # admission degenerates to the free-slot check. pool_labels /
        # pool_formats parallel the flat allocator list (submit errors,
        # stats).
        pool_specs: list[tuple] = []   # (spec, label, fmt) per pool
        dense_itemsize = jnp.dtype(rcfg.compute_dtype).itemsize
        comp_bytes = dense_bytes = 0
        for si, ((unit, _rep), stage) in enumerate(zip(cfg.stages,
                                                       self.caches)):
            for kind, node in zip(unit, stage):
                if not isinstance(node, PAGED_CACHE_TYPES):
                    continue
                tb = cache_lib.kv_token_bytes(node)
                layers = node.k_pages.shape[0]
                kv = node.k_pages.shape[-2]
                dense_tb = 2 * layers * kv * cfg.head_dim * dense_itemsize
                comp_bytes += tb
                dense_bytes += dense_tb
                fmt = self.cache_plan.cache_format(si, kind)
                pool_specs.append((
                    paging.spec_from_cache(node, tb),
                    f"stage{si}.{kind}",
                    str(fmt) if fmt else str(jnp.dtype(rcfg.compute_dtype)),
                ))
        self.replica_allocators = [
            [paging.PageAllocator(spec) for spec, _, _ in pool_specs]
            for _ in range(self.n_replicas if pool_specs else 1)
        ]
        self.allocators = [a for shard in self.replica_allocators
                           for a in shard]
        self.pool_labels: list[str] = []
        self.pool_formats: list[str] = []
        for rep in range(len(self.replica_allocators)):
            for _, label, fmt in pool_specs:
                self.pool_labels.append(
                    f"replica{rep}/{label}" if self.n_replicas > 1
                    else label)
                self.pool_formats.append(fmt)
        # bytes/token ratio vs an uncompressed pool set (1.0 when dense
        # or uncompressed paged) — the headline admission multiplier
        self.kv_compression_x = (dense_bytes / comp_bytes
                                 if comp_bytes else 1.0)
        self._kv_capacity_bytes = 0
        for node in cache_lib.kv_cache_nodes(self.caches):
            tb = cache_lib.kv_token_bytes(node)
            if isinstance(node, PAGED_CACHE_TYPES):
                pages, ps = cache_lib.pool_geometry(node)
                self._kv_capacity_bytes += pages * ps * tb
            else:
                self._kv_capacity_bytes += node.k.shape[1] * \
                    node.k.shape[2] * tb

        # prompt-length bucketing: off for archs whose prefill couples
        # rows/positions beyond causal attention (recurrent state, MoE
        # expert capacity) — pad tokens there would change the spliced
        # state, not just dead cache rows
        kinds = {k for unit, _ in cfg.stages for k in unit}
        coupled = sorted(kinds & {"rec", "ssm", "moe"})
        bucketable = not coupled
        self.prefill_buckets = (bucketable if prefill_buckets is None
                                else prefill_buckets and bucketable)
        if coupled and prefill_buckets is not False:
            arch = getattr(cfg, "name", "+".join(coupled))
            if arch not in _BUCKET_WARNED:
                _BUCKET_WARNED.add(arch)
                warnings.warn(
                    f"prefill buckets auto-disabled for arch {arch!r}: "
                    f"its {'/'.join(coupled)} blocks carry sequence-"
                    "coupled prefill state, so pad tokens would perturb "
                    "the spliced caches — every distinct prompt length "
                    "compiles its own prefill (engine stats() reports "
                    "buckets_enabled=False)", stacklevel=2)
        self.bucket_lens: set[int] = set()

        # --- copy-on-write prefix sharing + self-speculative decode ---
        self.prefix_share = bool(prefix_share)
        self.speculative_k = int(speculative_k)
        self.prefix_cache = int(prefix_cache)
        if self.speculative_k < 0 or self.prefix_cache < 0:
            raise ValueError("speculative_k and prefix_cache must be >= 0")
        if self.prefix_share:
            if self.cache_layout != "paged":
                raise ValueError(
                    "prefix_share adopts page-pool pages between requests; "
                    "the dense layout has no pages — pass "
                    "cache_layout='paged'")
            if self.n_replicas != 1:
                raise ValueError(
                    "prefix_share is single-replica: sharded pools keep "
                    "shard-local page ids, so adopting another slot's "
                    "pages could alias across shards — run one engine "
                    "per replica behind serve/router.py instead")
            if cfg.vision_tokens:
                raise ValueError(
                    "prefix_share identifies a prefix by its prompt "
                    "tokens alone; vision archs carry per-request image "
                    "state the index cannot compare")
            if any(spec.ring for spec, _, _ in pool_specs):
                raise ValueError(
                    "prefix_share needs append-only pools; ring "
                    "(sliding-window) pools overwrite their pages in "
                    "place, so an adopted prefix page would be clobbered "
                    "by the owner's later tokens")
        if self.speculative_k:
            if self.cache_layout != "paged":
                raise ValueError(
                    "speculative_k verifies k+1 draft rows in one fused "
                    "call through the paged flash-decode path — pass "
                    "cache_layout='paged'")
            bad = sorted(kinds - {"attn"})
            if bad:
                raise ValueError(
                    f"speculative_k needs every block to accept multi-row "
                    f"decode queries; {'/'.join(bad)} blocks are "
                    "sequential/windowed and verify row-by-row only")
        # prefix index: live entries keyed by uid; retired entries in an
        # LRU whose pages stay adoptable via ("prefix", uid) allocator
        # references until capacity pressure or the prefix_cache cap
        # evicts them. _prefix_bykey maps (n_full_pages, hash(prompt
        # prefix)) -> uid for O(pages) matching.
        self._prefix_live: dict[int, _PrefixEntry] = {}
        self._retired: "collections.OrderedDict[int, _PrefixEntry]" = \
            collections.OrderedDict()
        self._prefix_bykey: dict[tuple[int, int], int] = {}
        self._draft_donor: dict[int, list[int]] = {}
        self._donor_ok: dict[int, int] = {}
        self.prefix_hits = 0
        self.prefix_pages_adopted = 0
        self.cow_page_splits = 0
        self.spec_verify_calls = 0
        self.spec_tokens_drafted = 0
        self.spec_tokens_accepted = 0
        self._spec_fns: dict[int, callable] = {}

        self.queue: collections.deque[Request] = collections.deque()
        self._outputs: dict[int, list[int]] = {}
        self._prefill_s: dict[int, float] = {}
        self._requests: dict[int, Request] = {}
        # host stamps of requests that came through submit(), taken where
        # the serve.submit / serve.admit / serve.step spans open and close
        self._submit_t: dict[int, float] = {}
        self._queued_s: dict[int, float] = {}
        self._ttft_s: dict[int, float] = {}

        # aggregate stats
        self.prefill_tokens = 0
        self.prefill_time = 0.0
        self.insert_count = 0
        self.insert_time = 0.0
        self.decode_tokens = 0
        self.decode_time = 0.0
        # per-request queue waits and times to first token (seconds);
        # bounded rings so a long-lived engine's host memory stays flat
        self.queue_wait_samples: collections.deque[float] = \
            collections.deque(maxlen=65536)
        self.ttft_samples: collections.deque[float] = collections.deque(
            maxlen=65536)
        # high-water marks across steps (a drained engine reads 0 reserved,
        # so peaks are what the paged-vs-dense comparison wants)
        self.peak_active = 0
        self.peak_reserved_bytes = 0
        self.peak_used_bytes = 0

        cfg_, rcfg_, max_len_, plan_ = cfg, rcfg, max_len, self.plan
        # prompt_len rides as a traced operand so one compile covers every
        # true length inside a bucket (it only moves the logits gather and
        # the splice's pad mask)
        self._prefill_fn = jax.jit(
            lambda params, batch, plen: prefill(cfg_, rcfg_, params, batch,
                                                max_len_, plan_,
                                                prompt_len=plen))
        self._decode_fns: dict[int, callable] = {}
        # the engine never reuses the pre-call cache value, so on TPU the
        # cache buffers are donated — in-place slot splices and decode
        # blocks instead of a full-cache copy (and 2x peak cache memory)
        # per call. CPU donation is a measured pessimization; skip it.
        from repro.kernels.ops import on_tpu

        self._donate = (1,) if on_tpu() else ()
        donate0 = (0,) if on_tpu() else ()
        self._write_slot = jax.jit(
            lambda full, one, slot, plen: cache_lib.write_slot(
                full, cache_lib.mask_pad_rows(one, plen), slot),
            donate_argnums=donate0)
        self._write_slot_paged = jax.jit(cache_lib.write_slot_paged,
                                         donate_argnums=donate0)
        self._cow_fn = jax.jit(cache_lib.cow_split_pages,
                               donate_argnums=donate0)
        self._sample_first = jax.jit(self._sample_first_impl)

    # decode_state delegation: the pre-stage-API attribute surface
    caches = _state_prop("caches")
    slot_uid = _state_prop("slot_uid")
    tok = _state_prop("tok")
    pos = _state_prop("pos")
    remaining = _state_prop("remaining")
    gen_idx = _state_prop("gen_idx")
    active = _state_prop("active")
    seeds = _state_prop("seeds")
    temps = _state_prop("temps")
    topks = _state_prop("topks")
    eos_ids = _state_prop("eos_ids")

    # ------------------------------------------------------------------
    # compiled pieces
    # ------------------------------------------------------------------
    @staticmethod
    def _sample_first_impl(logits1, seed, temp, topk):
        return sample_tokens(logits1[None].astype(jnp.float32), seed[None],
                             jnp.zeros((1,), jnp.int32), temp[None],
                             topk[None])[0]

    def _get_decode(self, steps: int):
        """Jitted fused decode loop: ``steps`` tokens in one lax.scan.
        (jax.jit itself caches per prompt length on the prefill side; the
        scan length is a Python constant, hence the explicit dict here.)"""
        fn = self._decode_fns.get(steps)
        if fn is None:
            cfg, rcfg = self.cfg, self.rcfg
            vocab, max_len = cfg.vocab_size, self.max_len

            def loop(params, caches, tok, pos, active, remaining, gen_idx,
                     seeds, temps, topks, eos_ids):
                def body(carry, _):
                    caches, tok, pos, active, remaining, gen_idx = carry
                    safe_pos = cache_lib.park_positions(pos, active)[:, None]
                    logits, caches = decode_step(
                        cfg, rcfg, params, tok[:, None], safe_pos, caches)
                    logits1 = logits[:, 0, :vocab].astype(jnp.float32)
                    nxt = sample_tokens(logits1, seeds, gen_idx, temps, topks)
                    emitted = jnp.where(active, nxt, PAD_TOKEN)
                    was_active = active
                    stepped = active.astype(jnp.int32)
                    tok = jnp.where(active, nxt, tok)
                    pos = pos + stepped
                    remaining = remaining - stepped
                    gen_idx = gen_idx + stepped
                    active = (active & (remaining > 0) & (nxt != eos_ids)
                              & (pos < max_len - 1))
                    ys = (emitted, was_active)
                    return (caches, tok, pos, active, remaining, gen_idx), ys

                carry = (caches, tok, pos, active, remaining, gen_idx)
                carry, ys = jax.lax.scan(body, carry, None, length=steps)
                return carry, ys

            fn = jax.jit(loop, donate_argnums=self._donate)
            self._decode_fns[steps] = fn
        return fn

    def _get_spec_verify(self, k: int):
        """Jitted speculative verify: ONE decode_step over (B, k+1) rows
        — the last token plus k drafts — through the multi-row paged
        flash-decode path. Returns the greedy continuation at every row;
        row t's logits see exactly the tokens a sequential greedy decode
        would have seen IF drafts 1..t are correct, so the leading run of
        draft==greedy matches is exactly the sequential stream (causal
        masking keeps rows written for rejected drafts inert — they sit
        at future positions and are overwritten before anything reads
        them)."""
        fn = self._spec_fns.get(k)
        if fn is None:
            cfg, rcfg, vocab = self.cfg, self.rcfg, self.cfg.vocab_size

            def verify(params, caches, toks, pos, active):
                positions = jnp.where(
                    active[:, None],
                    pos[:, None] + jnp.arange(k + 1, dtype=jnp.int32)[None],
                    -1)
                logits, caches = decode_step(cfg, rcfg, params, toks,
                                             positions, caches)
                greedy = jnp.argmax(
                    logits[..., :vocab].astype(jnp.float32),
                    axis=-1).astype(jnp.int32)
                return caches, greedy

            fn = jax.jit(verify, donate_argnums=self._donate)
            self._spec_fns[k] = fn
        return fn

    def _ngram_draft(self, hist: list, n: int) -> list:
        """n cheap draft tokens from the request's own history: longest
        n-gram suffix match (3, 2, 1) over a bounded recent window, with
        repeat-last as the floor. Pure host work — never touches the
        model."""
        out: list[int] = []
        h = [int(x) for x in hist[-256:]]
        for _ in range(n):
            nxt = None
            for g in (3, 2, 1):
                if len(h) <= g:
                    continue
                pat = h[-g:]
                for i in range(len(h) - g - 1, -1, -1):
                    if h[i:i + g] == pat:
                        nxt = h[i + g]
                        break
                if nxt is not None:
                    break
            if nxt is None:
                nxt = h[-1]
            out.append(nxt)
            h.append(nxt)
        return out

    def _draft_tokens(self, uid: int, hist: list, k: int) -> list:
        """k draft tokens for a request. A donor stream (a retired
        request that shared the FULL prompt) drafts first — under greedy
        sampling the new request reproduces the donor's continuation
        verbatim until real divergence, so replayed traffic accepts at
        ~100%. ``_donor_ok`` tracks how much of the history has already
        been checked against the donor, keeping the validity check O(new
        tokens) per call instead of O(history)."""
        d: list[int] = []
        donor = self._draft_donor.get(uid)
        if donor is not None:
            ok = self._donor_ok.get(uid, 0)
            L = len(hist)
            while ok < L and ok < len(donor) and int(donor[ok]) == int(hist[ok]):
                ok += 1
            if ok < L:            # diverged from the donor: it is spent
                self._draft_donor.pop(uid, None)
                self._donor_ok.pop(uid, None)
            else:
                self._donor_ok[uid] = ok
                d = [int(x) for x in donor[L:L + k]]
        if len(d) < k:
            d.extend(self._ngram_draft(list(hist) + d, k - len(d)))
        return d[:k]

    def _generate_spec(self, params, decode_state: DecodeState
                       ) -> tuple[DecodeState, GenerateOutput]:
        """Speculative decode block: draft k tokens per active slot on
        the host, verify all of them in ONE fused (B, k+1) decode_step,
        then emit the leading accepted run plus the model's own next
        token — replicating the sequential loop's per-token stop
        semantics exactly. Rejected suffixes need no rollback: their
        cache rows sit at positions this slot has not reached, and the
        next call rewrites them before any query can attend that far."""
        ds = decode_state
        k = self.speculative_k
        B = self.max_slots
        t0 = time.perf_counter()
        drafts = np.zeros((B, k), np.int32)
        for b in range(B):
            if not ds.active[b]:
                continue
            uid = int(ds.slot_uid[b])
            req = self._requests.get(uid)
            if req is not None and uid in self._outputs:
                hist = [int(x) for x in req.tokens] + \
                    [int(x) for x in self._outputs[uid]]
            else:
                # stage-API use without the orchestrator's bookkeeping:
                # no history to mine, fall back to repeat-last
                hist = [int(ds.tok[b])]
            drafts[b] = np.asarray(self._draft_tokens(uid, hist, k),
                                   np.int32)
        fn = self._get_spec_verify(k)
        caches, greedy = fn(
            params, ds.caches,
            jnp.asarray(np.concatenate([ds.tok[:, None], drafts], axis=1)),
            jnp.asarray(ds.pos), jnp.asarray(ds.active))
        ds.caches = caches
        with span("serve.generate.wait"):
            greedy = np.array(greedy)                  # (B, k+1)
        emitted = np.full((k + 1, B), PAD_TOKEN, np.int32)
        was_active = np.zeros((k + 1, B), bool)
        n_act = int(ds.active.sum())
        self.spec_verify_calls += 1
        self.spec_tokens_drafted += k * n_act
        for b in range(B):
            if not ds.active[b]:
                continue
            a = 0
            while a < k and drafts[b, a] == greedy[b, a]:
                a += 1
            self.spec_tokens_accepted += a
            tok = int(ds.tok[b])
            pos = int(ds.pos[b])
            rem = int(ds.remaining[b])
            gi = int(ds.gen_idx[b])
            eos = int(ds.eos_ids[b])
            alive = True
            for t in range(a + 1):
                nxt = int(greedy[b, t])
                emitted[t, b] = nxt
                was_active[t, b] = True
                tok, pos, rem, gi = nxt, pos + 1, rem - 1, gi + 1
                if not (rem > 0 and nxt != eos and pos < self.max_len - 1):
                    alive = False
                    break
            ds.tok[b] = tok
            ds.pos[b] = pos
            ds.remaining[b] = rem
            ds.gen_idx[b] = gi
            ds.active[b] = alive
        self.decode_tokens += int(was_active.sum())
        self.decode_time += time.perf_counter() - t0
        return ds, GenerateOutput(emitted=emitted, was_active=was_active,
                                  steps=k + 1)

    # ------------------------------------------------------------------
    # stage API: prefill -> Prefix -> insert -> DecodeState -> generate
    # ------------------------------------------------------------------
    def prefill(self, params, request: Request) -> Prefix:
        """Run the prompt alone (batch 1) and package the result as a
        transferable :class:`Prefix` — the first token is sampled here
        from the prefill logits, so a decode replica receiving the Prefix
        never re-touches the prompt."""
        lp = len(request.tokens)
        lb = self._bucket_len(lp)
        with span("serve.prefill", uid=request.uid):
            toks = np.zeros((lb,), np.int32)
            toks[:lp] = np.asarray(request.tokens, np.int32)
            batch = {"tokens": jnp.asarray(toks)[None]}
            if self.cfg.vision_tokens:
                batch["image_embeds"] = jnp.asarray(
                    request.image_embeds, jnp.float32)[None]
            t0 = time.perf_counter()
            logits, pcaches = self._prefill_fn(params, batch,
                                               jnp.asarray([lp], jnp.int32))
            self.bucket_lens.add(lb)
            tok0 = self._sample_first(
                logits[0, -1, : self.cfg.vocab_size],
                jnp.int32(request.sampling.seed),
                jnp.float32(request.sampling.temperature),
                jnp.int32(request.sampling.top_k),
            )
            tok0 = int(tok0)
            jax.block_until_ready(pcaches)
            dt = time.perf_counter() - t0
        self.prefill_tokens += lp
        self.prefill_time += dt
        return Prefix(uid=request.uid, request=request, prompt_len=lp,
                      first_token=tok0, caches=pcaches, prefill_s=dt)

    def insert(self, prefix: Prefix, decode_state: DecodeState,
               slot: int) -> DecodeState:
        """Splice a Prefix into decode slot ``slot``: reserve pages from
        the slot's replica allocators (paged layout), install the caches,
        and arm the slot's sampling/stop vectors. Mutates and returns
        ``decode_state``.

        Raises on lifecycle violations — a consumed (stale) Prefix, or a
        slot that is not free — naming the slot's current state."""
        if prefix.consumed:
            raise ValueError(
                f"stale Prefix (uid={prefix.uid}): already inserted into "
                f"slot {prefix.inserted_slot}, which is now "
                f"{decode_state.slot_state(prefix.inserted_slot)}. A "
                "Prefix is single-use — re-run prefill to admit the "
                "request again")
        if decode_state.active[slot] or decode_state.slot_uid[slot] >= 0:
            raise ValueError(
                f"cannot insert Prefix (uid={prefix.uid}) into slot "
                f"{slot}: slot is {decode_state.slot_state(slot)} — wait "
                "for it to finish or place into a free slot")
        req = prefix.request
        lp = prefix.prompt_len
        with span("serve.insert", uid=req.uid):
            t0 = time.perf_counter()
            pcaches = prefix.caches
            if not isinstance(jax.tree.leaves(pcaches)[0], jax.Array):
                # host-transferred Prefix (router handoff): re-device the tree
                pcaches = jax.tree.map(jnp.asarray, pcaches)
            if self.allocators:
                share = self._match_prefix(req.tokens)
                rows, starts, srcs, dsts, flat_rows = self._alloc_rows(
                    req, slot, share)
                decode_state.caches = self._write_slot_paged(
                    decode_state.caches, pcaches, rows, jnp.int32(slot),
                    jnp.int32(lp), starts)
                m = 0 if share is None else share[1]
                lo = (m // self.page_size) * self.page_size
                if lo < m:
                    # the divergent page is fresh but its leading rows are
                    # still shared content: copy them from the owner's page
                    # BEFORE any decode write lands on this slot
                    decode_state.caches = self._cow_fn(
                        decode_state.caches, srcs, dsts, jnp.int32(lo),
                        jnp.int32(m))
                if self.prefix_share:
                    if (self.speculative_k and share is not None
                            and m == lp and share[0].stream):
                        # full-prompt hit on a retired request: its recorded
                        # continuation drafts this request's greedy stream
                        self._draft_donor[req.uid] = list(share[0].stream)
                        self._donor_ok[req.uid] = 0
                    self._register_prefix(req, flat_rows)
            else:
                decode_state.caches = self._write_slot(
                    decode_state.caches, pcaches, jnp.int32(slot),
                    jnp.int32(lp))
            jax.block_until_ready(decode_state.caches)
            self.insert_count += 1
            self.insert_time += time.perf_counter() - t0

        decode_state.slot_uid[slot] = req.uid
        decode_state.tok[slot] = prefix.first_token
        decode_state.pos[slot] = lp
        decode_state.remaining[slot] = req.max_new_tokens - 1
        decode_state.gen_idx[slot] = 1
        decode_state.seeds[slot] = req.sampling.seed
        decode_state.temps[slot] = req.sampling.temperature
        decode_state.topks[slot] = req.sampling.top_k
        decode_state.eos_ids[slot] = req.eos_id
        eos_hit = req.eos_id >= 0 and prefix.first_token == req.eos_id
        decode_state.active[slot] = (
            decode_state.remaining[slot] > 0 and not eos_hit
            and decode_state.pos[slot] < self.max_len - 1)
        prefix.consumed = True
        prefix.inserted_slot = slot
        return decode_state

    def generate(self, params, decode_state: DecodeState, *,
                 steps: int | None = None
                 ) -> tuple[DecodeState, GenerateOutput]:
        """One fused decode block over every active slot: ``steps`` tokens
        (default ``decode_block``, capped near the longest remaining
        generation) in a single jitted lax.scan. Mutates and returns
        ``decode_state`` plus the raw per-step emissions."""
        steps = steps or self.decode_block
        if not decode_state.active.any():
            B = decode_state.active.shape[0]
            return decode_state, GenerateOutput(
                emitted=np.full((0, B), PAD_TOKEN, np.int32),
                was_active=np.zeros((0, B), bool), steps=0)
        n_active = int(decode_state.active.sum())
        if self.speculative_k and not np.any(
                decode_state.temps[decode_state.active] > 0):
            # speculative verify is greedy-only (draft==argmax is the
            # acceptance rule); any sampling request in the batch drops
            # the whole block to the sequential loop so streams never mix
            # verify modes mid-request
            with span("serve.generate", steps=self.speculative_k + 1,
                      active=n_active):
                return self._generate_spec(params, decode_state)
        # Don't scan far past the longest remaining generation (inert
        # trailing iterations still run full decode steps over the batch),
        # but round tails up to a power of two: each distinct scan length
        # is a separate full-model compile, so an exact cap would pay
        # seconds of compilation to save milliseconds of masked steps.
        cap = max(1, int(decode_state.remaining[decode_state.active].max()))
        if cap < steps:
            steps = min(steps, 1 << (cap - 1).bit_length() if cap > 1 else 1)
        fn = self._get_decode(steps)
        with span("serve.generate", steps=steps, active=n_active):
            t0 = time.perf_counter()
            carry, (emitted, was_active) = fn(
                params, decode_state.caches,
                jnp.asarray(decode_state.tok), jnp.asarray(decode_state.pos),
                jnp.asarray(decode_state.active),
                jnp.asarray(decode_state.remaining),
                jnp.asarray(decode_state.gen_idx),
                jnp.asarray(decode_state.seeds),
                jnp.asarray(decode_state.temps),
                jnp.asarray(decode_state.topks),
                jnp.asarray(decode_state.eos_ids),
            )
            (decode_state.caches, tok, pos, active, remaining, gen_idx) = carry
            with span("serve.generate.wait"):
                emitted = np.asarray(emitted)      # (steps, B)
            was_active = np.asarray(was_active)    # (steps, B)
            self.decode_tokens += int(was_active.sum())
            self.decode_time += time.perf_counter() - t0

            # np.array (not asarray): device arrays view as read-only buffers
            decode_state.tok = np.array(tok)
            decode_state.pos = np.array(pos)
            decode_state.remaining = np.array(remaining)
            decode_state.gen_idx = np.array(gen_idx)
            decode_state.active = np.array(active)
        return decode_state, GenerateOutput(emitted=emitted,
                                            was_active=was_active,
                                            steps=steps)

    # ------------------------------------------------------------------
    # scheduling
    # ------------------------------------------------------------------
    def _validate_request(self, req: Request) -> None:
        """Raise if the request can NEVER be served by this engine (bad
        sizes, or a per-replica pool it cannot fit in)."""
        lp = len(req.tokens)
        if lp < 1 or req.max_new_tokens < 1:
            raise ValueError(f"request {req.uid}: empty prompt or generation")
        if lp + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {req.uid}: prompt_len={lp} + max_new_tokens="
                f"{req.max_new_tokens} exceeds max_len={self.max_len}")
        if self.cfg.vision_tokens and req.image_embeds is None:
            raise ValueError(f"request {req.uid}: arch needs image_embeds")
        for alloc, label, fmt in zip(self.allocators, self.pool_labels,
                                     self.pool_formats):
            need = alloc.blocks_for(lp + req.max_new_tokens)
            if need > alloc.spec.n_pages:
                total = lp + req.max_new_tokens
                cap_tok = alloc.spec.n_pages * alloc.spec.page_size
                raise ValueError(
                    f"request {req.uid}: needs {need} pages "
                    f"({total} tokens) but pool {label} [{fmt}] has "
                    f"{alloc.spec.n_pages} pages ({cap_tok} tokens) total "
                    f"— {total - cap_tok} tokens over capacity; raise "
                    f"pool_tokens or shrink prompt_len + max_new_tokens")

    def submit(self, req: Request) -> None:
        with span("serve.submit", uid=req.uid):
            self._validate_request(req)
            self.queue.append(req)
            self._submit_t[req.uid] = time.perf_counter()

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or bool(self.active.any())

    def _free_slots(self) -> list[int]:
        return [int(i) for i in np.nonzero(~self.active)[0]]

    def _slot_replica(self, slot: int) -> int:
        """Replica shard owning ``slot`` (contiguous-chunk map; 0 when
        single-host)."""
        return slot // (self.max_slots // self.n_replicas)

    def _bucket_len(self, lp: int) -> int:
        """Pad target for a prompt of ``lp`` tokens: the next power of two
        (>= 16), capped at max_len — a handful of prefill compiles total
        instead of one per distinct prompt length."""
        if not self.prefill_buckets:
            return lp
        b = 16
        while b < lp:
            b <<= 1
        return min(b, self.max_len)

    # ------------------------------------------------------------------
    # copy-on-write prefix index
    # ------------------------------------------------------------------
    def _match_prefix(self, tokens) -> tuple[_PrefixEntry, int] | None:
        """Longest live/retired prefix match for a prompt: ``(entry, m)``
        with ``m`` the matched token count. Probes the index from the
        longest full-page prefix down; the token-equality re-check guards
        hash collisions, and the partial-page extension stays confined to
        the first divergent page (that page is the ONE copy-on-write
        split an admission performs)."""
        if not self.prefix_share:
            return None
        t = tuple(int(x) for x in tokens)
        ps = self.page_size
        for j in range(len(t) // ps, 0, -1):
            key = (j, hash(t[: j * ps]))
            uid = self._prefix_bykey.get(key)
            if uid is None:
                continue
            entry = self._prefix_live.get(uid) or self._retired.get(uid)
            if entry is None:
                del self._prefix_bykey[key]   # evicted owner, stale key
                continue
            if entry.tokens[: j * ps] != t[: j * ps]:
                continue                       # hash collision
            m = j * ps
            lim = min(len(entry.tokens), len(t), (j + 1) * ps)
            while m < lim and entry.tokens[m] == t[m]:
                m += 1
            if entry.retired:
                self._retired.move_to_end(uid)  # LRU touch
            return entry, m
        return None

    def _register_prefix(self, req: Request, flat_rows: list) -> None:
        """Index a just-admitted request as a live prefix owner."""
        t = tuple(int(x) for x in req.tokens)
        self._prefix_live[req.uid] = _PrefixEntry(uid=req.uid, tokens=t,
                                                  rows=flat_rows)
        for j in range(1, len(t) // self.page_size + 1):
            self._prefix_bykey[(j, hash(t[: j * self.page_size]))] = req.uid

    def _unindex_prefix(self, entry: _PrefixEntry) -> None:
        for j in range(1, len(entry.tokens) // self.page_size + 1):
            key = (j, hash(entry.tokens[: j * self.page_size]))
            if self._prefix_bykey.get(key) == entry.uid:
                del self._prefix_bykey[key]

    def _drop_retired(self, uid: int) -> None:
        """Evict a retired prefix entry: drop its ("prefix", uid) page
        references (pages free once no adopter still maps them)."""
        entry = self._retired.pop(uid)
        for alloc in self.replica_allocators[0]:
            alloc.release(("prefix", uid))
        self._unindex_prefix(entry)

    def _evict_one_retired(self) -> bool:
        """Free the least-recently-matched retired prefix (page
        pressure); returns False when nothing is left to evict."""
        if not self._retired:
            return False
        self._drop_retired(next(iter(self._retired)))
        return True

    def _retire_prefix(self, uid: int, generated: list) -> None:
        """Move a finishing request's entry live -> retired: retain its
        PROMPT pages under a ("prefix", uid) reference (must run before
        the slot's own release) and record the full token stream as a
        speculative draft donor. Oldest retirees fall off the LRU cap."""
        entry = self._prefix_live.pop(uid, None)
        if entry is None:
            return
        if self.prefix_cache == 0:
            self._unindex_prefix(entry)
            return
        n_prompt_pages = -(-len(entry.tokens) // self.page_size)
        for alloc, row in zip(self.replica_allocators[0], entry.rows):
            alloc.retain(("prefix", uid), row[:n_prompt_pages])
        entry.stream = list(entry.tokens) + [int(x) for x in generated]
        entry.retired = True
        self._retired[uid] = entry
        while len(self._retired) > self.prefix_cache:
            self._drop_retired(next(iter(self._retired)))

    def _can_admit(self, req: Request) -> bool:
        """Paged admission predicate: SOME replica shard has enough free
        pages in EVERY one of its pools for the request's full reservation
        (prompt + worst-case generation — a reserved request can always
        run to its stop condition; no mid-stream preemption). Prefix
        sharing charges only the NON-shared page delta — the adopted
        prefix pages are live already. Dense layout: a free slot is
        enough."""
        if not self.allocators:
            return True
        total = len(req.tokens) + req.max_new_tokens
        match = self._match_prefix(req.tokens)
        s = 0 if match is None else match[1] // self.page_size
        return any(
            all(a.can_allocate(a.blocks_for(total) - s) for a in pools)
            for pools in self.replica_allocators)

    def try_place(self, req: Request) -> int | None:
        """Page-aware placement: the slot the request should be admitted
        to, or None if nothing fits right now. Deterministic — among
        replicas with a free slot AND page room in every pool, pick the
        one with the most post-admission headroom in its tightest pool
        (ties: lowest replica index), then its lowest free slot. A
        single-host engine degenerates to first-free-slot + the pool
        check, exactly the old behavior."""
        free = self._free_slots()
        if not free:
            return None
        if not self.allocators:
            return free[0]
        total = len(req.tokens) + req.max_new_tokens
        while True:
            # rematch every iteration: evicting a retired prefix below
            # may remove the entry we just matched, and the placement we
            # return must reflect the allocator state we leave behind
            match = self._match_prefix(req.tokens)
            s = 0 if match is None else match[1] // self.page_size
            best: tuple[int, int] | None = None
            for rep, pools in enumerate(self.replica_allocators):
                rep_free = [x for x in free if self._slot_replica(x) == rep]
                if not rep_free:
                    continue
                if not all(a.can_allocate(a.blocks_for(total) - s)
                           for a in pools):
                    continue
                headroom = min(a.free_pages - (a.blocks_for(total) - s)
                               for a in pools)
                if best is None or headroom > best[0]:
                    best = (headroom, rep_free[0])
            if best is not None:
                return best[1]
            # page pressure: retired prefixes are a cache, not a
            # reservation — give their pages back one LRU entry at a
            # time and retry until the head request fits or nothing is
            # left to evict
            if not self._evict_one_retired():
                return None

    def pool_load(self) -> float:
        """Load factor in [0, 1] for router placement: the tightest
        pool's reserved fraction across replica shards (paged), or the
        occupied-slot fraction (dense)."""
        if not self.allocators:
            return float(self.active.sum()) / max(1, self.max_slots)
        return max(a.reserved_pages / max(1, a.spec.n_pages)
                   for a in self.allocators)

    def _alloc_rows(self, req: Request, slot: int, share=None):
        """Reserve pages in every pool of the slot's replica shard;
        returns ``(rows, starts, srcs, dsts, flat_rows)``:

        * ``rows`` — block-table rows tree (aligned with the cache tree:
          a (nb,) row of shard-LOCAL page ids per paged node, None
          elsewhere) for write_slot_paged;
        * ``starts`` — same-shaped tree of int32 scalars: the prefix-
          share boundary ``m`` in tokens (0 unshared) — the splice must
          not touch the adopted pages below it;
        * ``srcs``/``dsts`` — same-shaped trees of int32 page-id scalars
          for the copy-on-write split of the divergent page (-1 when the
          boundary is page-aligned and no copy is needed);
        * ``flat_rows`` — the numpy rows in flat pool order (prefix-index
          registration).

        ``share`` is a ``(entry, m)`` match from :meth:`_match_prefix`:
        the first ``m // page_size`` FULL pages of the entry's rows are
        adopted (refcount bump, no free-list charge); every pool shares
        one ``page_size``, so the boundary is common to all of them."""
        total = len(req.tokens) + req.max_new_tokens
        pools = self.replica_allocators[self._slot_replica(slot)]
        ps = self.page_size
        entry, m = share if share is not None else (None, 0)
        s = m // ps
        need_cow = s * ps < m
        ai = 0
        rows, starts, srcs, dsts = [], [], [], []
        flat_rows: list[np.ndarray] = []
        for stage in self.caches:
            rstage, ststage, srcstage, dststage = [], [], [], []
            for node in stage:
                if isinstance(node, PAGED_CACHE_TYPES):
                    alloc = pools[ai]
                    ai += 1
                    shared = None if entry is None else entry.rows[ai - 1][:s]
                    row = alloc.allocate(slot, alloc.blocks_for(total),
                                         shared=shared)
                    flat_rows.append(np.array(row))
                    rstage.append(jnp.asarray(row))
                    ststage.append(jnp.int32(m))
                    srcstage.append(jnp.int32(
                        int(entry.rows[ai - 1][s]) if need_cow else -1))
                    dststage.append(jnp.int32(
                        int(row[s]) if need_cow else -1))
                else:
                    rstage.append(None)
                    ststage.append(None)
                    srcstage.append(None)
                    dststage.append(None)
            rows.append(rstage)
            starts.append(ststage)
            srcs.append(srcstage)
            dsts.append(dststage)
        if m:
            self.prefix_hits += 1
            self.prefix_pages_adopted += s * ai
            if need_cow:
                self.cow_page_splits += ai
        return rows, starts, srcs, dsts, flat_rows

    def _admit(self, req: Request, slot: int) -> Optional[RequestOutput]:
        """Orchestrated admission: prefill + insert + bookkeeping, and the
        request's time in the queue."""
        lp = len(req.tokens)
        with span("serve.admit", uid=req.uid, prompt_len=lp,
                  bucket=self._bucket_len(lp)):
            t_sub = self._submit_t.get(req.uid)
            if t_sub is not None:
                wait = time.perf_counter() - t_sub
                self._queued_s[req.uid] = wait
                self.queue_wait_samples.append(wait)
            return self.admit_prefix(self.prefill(self.params, req), slot)

    def admit_prefix(self, prefix: Prefix,
                     slot: int) -> Optional[RequestOutput]:
        """Insert an (possibly handed-off) Prefix and register its request
        with the orchestrator's output bookkeeping. Returns the finished
        RequestOutput when the first token already hit a stop condition."""
        self.decode_state = self.insert(prefix, self.decode_state, slot)
        req = prefix.request
        self._requests[req.uid] = req
        self._outputs[req.uid] = [prefix.first_token]
        self._prefill_s[req.uid] = prefix.prefill_s
        if not self.active[slot]:
            return self._finish(slot)
        return None

    def _finish(self, slot: int) -> RequestOutput:
        uid = int(self.slot_uid[slot])
        req = self._requests.pop(uid)
        toks = self._outputs.pop(uid)
        reason = ("eos" if req.eos_id >= 0 and toks and toks[-1] == req.eos_id
                  else "length")
        out = RequestOutput(
            uid=uid,
            prompt_len=len(req.tokens),
            tokens=toks,
            finish_reason=reason,
            prefill_s=self._prefill_s.pop(uid),
            queued_s=self._queued_s.pop(uid, None),
            ttft_s=self._ttft_s.pop(uid, None),
        )
        self.slot_uid[slot] = -1
        self.active[slot] = False
        self.pos[slot] = -1
        # prefix retirement must precede the slot release: the entry's
        # prompt pages pick up their ("prefix", uid) reference while the
        # slot still holds them live
        if self.prefix_share:
            self._retire_prefix(uid, toks)
        self._draft_donor.pop(uid, None)
        self._donor_ok.pop(uid, None)
        # paged reclamation: pages go back to the host free list; the
        # device cache is untouched (no live block table maps them). Only
        # the slot's own replica shard ever allocated for it — release on
        # the others is a no-op.
        for alloc in self.allocators:
            alloc.release(slot)
        # reset sampling state: a stale temperature > 0 on a free slot
        # would keep defeating sample_tokens' all-greedy lax.cond fast path
        self.temps[slot] = 0.0
        self.topks[slot] = 0
        self.seeds[slot] = 0
        self.eos_ids[slot] = -1
        return out

    # ------------------------------------------------------------------
    # engine loop (thin orchestrator over the stage API)
    # ------------------------------------------------------------------
    def step(self, *, decode_steps: int | None = None) -> list[RequestOutput]:
        """Admit what fits, then run one fused decode block. Returns the
        requests that finished during this step. A request admitted here
        has its first token when the step returns: that is its ``ttft_s``."""
        finished: list[RequestOutput] = []
        admitted: list[int] = []
        with span("serve.step"):
            while self.queue:
                slot = self.try_place(self.queue[0])
                if slot is None:
                    # strict FIFO: when the head can't get a slot + pages,
                    # later (maybe smaller) requests wait too — admission
                    # order, and hence every token stream, stays
                    # deterministic
                    break
                req = self.queue.popleft()
                admitted.append(req.uid)
                done = self._admit(req, slot)
                if done is not None:
                    finished.append(done)

            self.peak_active = max(self.peak_active, int(self.active.sum()))
            reserved, used, _, _ = self._cache_usage()
            self.peak_reserved_bytes = max(self.peak_reserved_bytes, reserved)
            self.peak_used_bytes = max(self.peak_used_bytes, used)

            if self.active.any():
                prev_active = self.active.copy()
                self.decode_state, out = self.generate(
                    self.params, self.decode_state, steps=decode_steps)
                with span("serve.bookkeep"):
                    # used peaks AFTER the decode block lands (positions
                    # advanced, slots not yet released) — the admission-time
                    # sample above only covers the prompt tokens
                    _, used, _, _ = self._cache_usage()
                    self.peak_used_bytes = max(self.peak_used_bytes, used)
                    for b in range(self.max_slots):
                        uid = int(self.slot_uid[b])
                        if uid < 0:
                            continue
                        for t in range(out.steps):
                            if out.was_active[t, b]:
                                self._outputs[uid].append(int(out.emitted[t, b]))
                        if prev_active[b] and not self.active[b]:
                            finished.append(self._finish(b))
            t_end = time.perf_counter()
        for uid in admitted:
            t_sub = self._submit_t.pop(uid, None)
            if t_sub is not None:
                self._ttft_s[uid] = t_end - t_sub
                self.ttft_samples.append(t_end - t_sub)
        for out in finished:
            if out.uid in admitted:
                out.ttft_s = self._ttft_s.pop(out.uid, None)
        return finished

    def run(self, requests: Sequence[Request]) -> dict[int, RequestOutput]:
        """Submit everything, drive steps until drained."""
        for r in requests:
            self.submit(r)
        done: dict[int, RequestOutput] = {}
        while self.has_work:
            for out in self.step():
                done[out.uid] = out
        return done

    # ------------------------------------------------------------------
    # stats
    # ------------------------------------------------------------------
    def reset_stats(self) -> None:
        """Zero the aggregate counters (e.g. after a compile warmup pass);
        compiled functions and slot state are kept."""
        self.prefill_tokens = 0
        self.prefill_time = 0.0
        self.insert_count = 0
        self.insert_time = 0.0
        self.decode_tokens = 0
        self.decode_time = 0.0
        self.queue_wait_samples.clear()
        self.ttft_samples.clear()
        self.peak_active = 0
        self.peak_reserved_bytes = 0
        self.peak_used_bytes = 0
        self.prefix_hits = 0
        self.prefix_pages_adopted = 0
        self.cow_page_splits = 0
        self.spec_verify_calls = 0
        self.spec_tokens_drafted = 0
        self.spec_tokens_accepted = 0

    def _cache_usage(self) -> tuple[int, int, int, int]:
        """(reserved_bytes, used_bytes, pages_total, pages_free) right now.

        Dense: every occupied slot reserves its whole ``max_len`` slab.
        Paged: reserved = pages handed out by the allocators. ``used`` is
        tokens actually written either way, so the utilization gap IS the
        memory the paged layout gives back.
        """
        occupied = np.nonzero(self.slot_uid >= 0)[0]
        reserved = used = 0
        pages_total = pages_free = 0
        if self.allocators:
            for alloc in self.allocators:
                pages_total += alloc.spec.n_pages
                pages_free += alloc.free_pages
                reserved += alloc.reserved_bytes
                # per-replica allocators own only their shard's slots
                used += alloc.spec.token_bytes * sum(
                    alloc.used_tokens(int(self.pos[s])) for s in occupied
                    if alloc.owns(int(s)))
        else:
            for node in cache_lib.kv_cache_nodes(self.caches):
                S = node.k.shape[2]
                tb = cache_lib.kv_token_bytes(node)
                reserved += len(occupied) * S * tb
                used += tb * sum(
                    min(max(int(self.pos[s]), 0), S) for s in occupied)
        return reserved, used, pages_total, pages_free

    def cache_telemetry(self) -> dict:
        """Reserved-vs-used KV telemetry (core.stats.serving_cache_metrics)."""
        reserved, used, pages_total, pages_free = self._cache_usage()
        return stats_lib.serving_cache_metrics(
            reserved_bytes=reserved, used_bytes=used,
            capacity_bytes=self._kv_capacity_bytes,
            pages_total=pages_total, pages_free=pages_free,
            compression_x=self.kv_compression_x)

    def stats(self) -> dict:
        waits = sorted(self.queue_wait_samples)
        ttfts = sorted(self.ttft_samples)
        out = {
            "prefill_tokens": self.prefill_tokens,
            "prefill_s": self.prefill_time,
            "prefill_tok_s": (self.prefill_tokens / self.prefill_time
                              if self.prefill_time else 0.0),
            "insert_count": self.insert_count,
            "insert_s": self.insert_time,
            "insert_ms_avg": (1e3 * self.insert_time / self.insert_count
                              if self.insert_count else 0.0),
            "decode_tokens": self.decode_tokens,
            "decode_s": self.decode_time,
            "decode_tok_s": (self.decode_tokens / self.decode_time
                             if self.decode_time else 0.0),
            # per request, from submit(): to its admission, and to the
            # return of the step that admitted it (its first token)
            "queue_wait_ms_p50": _percentile(waits, 0.50) * 1e3,
            "queue_wait_ms_p90": _percentile(waits, 0.90) * 1e3,
            "ttft_ms_p50": _percentile(ttfts, 0.50) * 1e3,
            "ttft_ms_p90": _percentile(ttfts, 0.90) * 1e3,
            "cache_slot_bytes": cache_lib.slot_bytes(self.caches, self.max_slots),
            "prefill_compiles": len(self.bucket_lens),
            "buckets_enabled": self.prefill_buckets,
            "replica_shards": self.n_replicas,
            "prefix_share": self.prefix_share,
            "prefix_hits": self.prefix_hits,
            "prefix_pages_adopted": self.prefix_pages_adopted,
            "cow_page_splits": self.cow_page_splits,
            "shared_pages_now": sum(a.shared_pages for a in self.allocators),
            "retired_prefixes": len(self._retired),
            "speculative_k": self.speculative_k,
            "spec_verify_calls": self.spec_verify_calls,
            "spec_tokens_drafted": self.spec_tokens_drafted,
            "spec_tokens_accepted": self.spec_tokens_accepted,
            "spec_accept_rate": (self.spec_tokens_accepted
                                 / self.spec_tokens_drafted
                                 if self.spec_tokens_drafted else 0.0),
            "peak_active": self.peak_active,
            "peak_kv_reserved_bytes": self.peak_reserved_bytes,
            "peak_kv_used_bytes": self.peak_used_bytes,
            # per-site cache-compression telemetry: pool label -> stored
            # format and true bytes/token (scales included)
            "cache_pools": {
                label: {"format": fmt,
                        "token_bytes": alloc.spec.token_bytes,
                        "pages": alloc.spec.n_pages}
                for label, fmt, alloc in zip(
                    self.pool_labels, self.pool_formats, self.allocators)
            },
        }
        out.update(self.cache_telemetry())
        return out
