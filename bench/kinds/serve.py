"""Serving cells: the program's continuous-batching engine (``submit`` and
``step``) over paged KV pools, greedy decoding.

Open loop (``loop: open``): requests are due at Poisson arrival times; each
is submitted at its due time or, when an engine step is running then, right
after it. Closed loop (``loop: closed``): ``clients`` clients each submit
their next request when the step that finished the previous one returns.

A token counts as delivered when the ``engine.step()`` that produced it
returns. The window opens ``pre_window_s`` after the generator starts. Time
to first token runs from a request's due time to the return of the step
that delivered its first token; after the window closes the generator
keeps sending until every request due in the window has its first token,
or ``drain_s`` has passed, when one with none counts at (that time - due)
and as failed. The gap between tokens of a request is (last token - first
token) / (tokens - 1) over the tokens it had delivered by then.

Set-up makes the weights from the seed (one jitted call), builds the
engine, and serves one request per prompt bucket and per decode-block
length the traffic can reach, so every program the window runs is compiled
or loaded from the compile cache before the window.
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, traffic as traffic_lib

WEIGHT_STREAM = 0


def nearest_rank(values, p: float) -> float:
    v = sorted(values)
    return v[max(1, math.ceil(p * len(v))) - 1]


def build(cell, seed: int):
    from repro.configs import RunConfig
    from repro.serve import ServeEngine

    cfg = cell["config_file"]
    st = cell["settings"]
    fam = common.load_module("families", cfg["family"])
    ref = common.load_module("reference", cfg["family"])
    mcfg = fam.model_config(cfg)
    run = cfg["run"]
    rcfg = RunConfig(compute_dtype=run["compute_dtype"], param_dtype=run["param_dtype"],
                     policy_name="none", attn_kernel="auto",
                     cache_layout=run["cache_layout"], kv_page_size=run["page_size"])
    key = jax.random.fold_in(jax.random.key(seed), WEIGHT_STREAM)
    dtype = jnp.dtype(run["param_dtype"])
    params = jax.jit(lambda k: fam.to_program(ref.init_weights(cfg, k, dtype)))(key)
    engine = ServeEngine(mcfg, rcfg, params, max_slots=st["max_slots"],
                         max_len=st["max_len"], decode_block=st["decode_block"],
                         cache_layout=run["cache_layout"], page_size=run["page_size"],
                         pool_tokens=st["pool_tokens"])
    return dict(cfg=cfg, fam=fam, ref=ref, key=key, dtype=dtype, engine=engine)


class Recorder:
    """Wraps the engine's public stage calls to learn, per step, which
    prompts were prefilled (at which bucket) and which slots decoded at
    which positions: the kernel calls and model FLOPs of the window."""

    def __init__(self, engine, fault: str | None = None):
        self.engine = engine
        self.on = False
        self.prefills = []       # (prompt_len, bucket_len)
        self.decode_iters = []   # per decode iteration: positions of the active slots
        orig_prefill, orig_generate = engine.prefill, engine.generate

        def prefill(params, request):
            out = orig_prefill(params, request)
            if self.on:
                lp = len(request.tokens)
                self.prefills.append((lp, engine._bucket_len(lp)))
            return out

        def generate(params, state, *, steps=None):
            pos0 = np.array(state.pos)
            state, out = orig_generate(params, state, steps=steps)
            if fault == "token" and out.steps:
                # alter each slot's first token of the block where it is
                # produced (the device carries the true one on)
                act = out.was_active[0]
                out.emitted = np.array(out.emitted)
                out.emitted[0, act] = (out.emitted[0, act] + 1) % engine.cfg.vocab_size
            if self.on and out.steps:
                for t in range(out.steps):
                    self.decode_iters.append(pos0[out.was_active[t]] + t)
            return state, out

        engine.prefill = prefill
        engine.generate = generate


def warm(engine, traffic: dict, vocab: int):
    """Serve, one at a time, a request per prompt bucket the traffic can
    reach and per decode-block length (8, 4, 2, 1 tokens)."""
    from repro.serve import Request, SamplingParams

    lo, hi = traffic["prompt"]["min"], traffic["prompt"]["max"]
    buckets = sorted({engine._bucket_len(n) for n in range(lo, hi + 1)})
    lengths = [min(max(b, lo), hi) for b in buckets]
    uid = 10**9  # clear of the run's request ids (uids must be >= 0)
    greedy = SamplingParams(temperature=0.0)
    tok = np.arange(max(lengths), dtype=np.int32) % vocab
    for i, n in enumerate(lengths):
        for new in ((9, 5, 3, 2) if i == 0 else (2,)):
            engine.run([Request(uid=uid, tokens=tok[:n], max_new_tokens=new,
                                sampling=greedy)])
            uid += 1
    engine.reset_stats()
    return buckets


def run(cell, args, devices, spans, counter, window_open, window_close):
    from repro.serve import Request, SamplingParams

    tr = cell["traffic_file"]
    st = cell["settings"]
    cfg = cell["config_file"]
    vocab = cfg["vocab_size"]
    b = build(cell, args.seed)
    engine = b["engine"]
    rec = Recorder(engine, getattr(args, "fault", None))
    buckets = warm(engine, tr, vocab)
    common.log(f"warmed prompt buckets {buckets}")

    open_loop = tr["loop"] == "open"
    n = (traffic_lib.open_loop_count(tr, args.seconds) if open_loop
         else traffic_lib.closed_loop_count(tr, args.seconds))
    reqs = traffic_lib.requests(tr, n, args.seed, vocab)
    greedy = SamplingParams(temperature=0.0)
    due = np.full(n, np.nan)
    submit_t = np.full(n, np.nan)
    first_t = np.full(n, np.nan)
    last_t = np.full(n, np.nan)
    count = np.zeros(n, np.int64)
    finished = {}
    next_req = 0
    delivered = 0          # tokens delivered in the window
    admit0 = decode0 = None

    def submit(i, t):
        engine.submit(Request(uid=i, tokens=reqs[i].prompt,
                              max_new_tokens=reqs[i].max_new, sampling=greedy))
        submit_t[i] = t

    tg0 = common.now()
    w0 = tg0 + tr["pre_window_s"]
    w1 = w0 + args.seconds
    if open_loop:
        due[:] = tg0 + np.array([r.due for r in reqs])
    else:
        for _ in range(tr["clients"]):
            due[next_req] = tg0
            submit(next_req, tg0)
            next_req += 1
    in_window = False
    closed_at = drained_at = None
    t = tg0
    while True:
        t = common.now()
        if not in_window and closed_at is None and t >= w0:
            in_window = True
            w0 = t
            w1 = w0 + args.seconds
            window_open()
            counter.armed = True
            rec.on = True
            stats0 = engine.stats()
            admit0 = stats0["prefill_s"] + stats0["insert_s"]
            decode0 = (stats0["decode_tokens"], len(rec.decode_iters))
            span = spans("window")
            span.__enter__()
        if in_window and t >= w1:
            in_window = False
            closed_at = t
            span.__exit__(None, None, None)
            rec.on = False
            counter.armed = False
            window_close()
            stats1 = engine.stats()
            common.log("window engine counters: " + ", ".join(
                f"{k} {stats1[k] - stats0[k]:.6g}" for k in
                ("insert_count", "prefill_tokens", "prefill_s", "insert_s",
                 "decode_tokens", "decode_s")) +
                f", decode iterations {len(rec.decode_iters)}, queue {len(engine.queue)}")
        if closed_at is not None and drained_at is None:
            pending = [i for i in range(next_req) if math.isnan(first_t[i])
                       and w0 <= due[i] < closed_at]
            if not pending or t >= closed_at + tr["drain_s"]:
                drained_at = t
                stalled = set(pending)
        if drained_at is not None:
            # the comparison needs finished requests: serve on, sending
            # nothing new, until enough have finished (a minute at most)
            served = sum(len(o.tokens) for o in finished.values())
            if (served >= st["sample_tokens"] or len(finished) >= st["sample_most"]
                    or t >= drained_at + 60 or not engine.has_work):
                break
        elif open_loop:
            with spans("submit"):
                while next_req < n and due[next_req] <= t:
                    submit(next_req, t)
                    next_req += 1
        if not engine.has_work:
            if open_loop and next_req < n:
                with spans("wait"):
                    time.sleep(max(0.0, min(due[next_req] - common.now(), 0.05)))
                continue
            break
        with spans("engine_step"):
            done = engine.step()
        tr_ = common.now()
        got = 0
        for s in np.nonzero(engine.slot_uid >= 0)[0]:
            uid = int(engine.slot_uid[s])
            g = int(engine.gen_idx[s])
            if g > count[uid]:
                got += g - count[uid]
                count[uid] = g
                last_t[uid] = tr_
                if math.isnan(first_t[uid]):
                    first_t[uid] = tr_
        for out in done:
            uid = out.uid
            g = len(out.tokens)
            if g > count[uid]:
                got += g - count[uid]
                count[uid] = g
                last_t[uid] = tr_
                if math.isnan(first_t[uid]):
                    first_t[uid] = tr_
            finished[uid] = out
            if not open_loop and next_req < n and drained_at is None:
                due[next_req] = tr_
                with spans("submit"):
                    submit(next_req, tr_)
                next_req += 1
        if in_window:
            delivered += got
    peak = common.peak_bytes(devices)

    # metrics over the requests due in the window
    win = [i for i in range(next_req) if w0 <= due[i] < closed_at]
    ttft = [(drained_at if i in stalled else first_t[i]) - due[i] for i in win]
    tpot = [(last_t[i] - first_t[i]) / (count[i] - 1) for i in win if count[i] > 1]
    late = [submit_t[i] - due[i] for i in win]
    window_s = closed_at - w0
    e2e = {
        "ttft_p90_ms": 1e3 * nearest_rank(ttft, 0.9) if ttft else math.inf,
        "tpot_p90_ms": 1e3 * nearest_rank(tpot, 0.9) if tpot else math.inf,
        "serve_tokens_per_s": delivered / window_s,
    }
    common.log(f"window {window_s:.3f} s: {len(win)} requests due, "
               f"{sum(1 for i in win if i in finished)} finished, {delivered} "
               f"tokens delivered, ttft p50 {1e3 * nearest_rank(ttft, 0.5) if ttft else 0:.1f} ms, "
               f"queue at close {len(engine.queue)}")

    # kernel calls and model FLOPs of the window
    fam, ref = b["fam"], b["ref"]
    s = ref.sizes(cfg)
    attn = dict(H=s["h"], KV=s["kv"], dh=s["dh"])
    mm = fam.matmul_flops_per_token(cfg)
    flops = 0.0
    fwd_calls, dec_calls = [], []
    for lp, lb in rec.prefills:
        flops += mm * lp + fam.attention_flops(cfg, lp * (lp + 1) / 2)
        fwd_calls += [dict(B=1, Lq=lb, Lk=lb, causal=True, itemsize=2, **attn)] * s["layers"]
    for pos in rec.decode_iters:
        flops += mm * len(pos) + fam.attention_flops(cfg, float(np.sum(pos + 1)))
        dec_calls += [dict(ctx=[int(p) + 1 for p in pos], **attn)] * s["layers"]
    ctx = dict(kind="serve", model_flops=flops,
               kernel_calls={"flash_fwd": fwd_calls, "paged_decode": dec_calls},
               admit_s=stats1["prefill_s"] + stats1["insert_s"] - admit0,
               decode_tokens=stats1["decode_tokens"] - decode0[0],
               decode_steps=sum(1 for p in rec.decode_iters[decode0[1]:] if len(p)),
               late_p99_ms=1e3 * nearest_rank(late, 0.99) if late else None)

    # correctness: a sample of finished requests, the longest in it, once
    # the engine (weights, pools) is freed
    import gc

    rec.engine = engine = b["engine"] = None
    gc.collect()
    if open_loop:
        # every request due in the window, and those with no first token by
        # the end of the drain
        attempted = len(win)
        failed = len(stalled)
    else:
        # every request in service in the window (a closed loop's requests
        # outlast it); none is refused
        attempted = sum(1 for i in range(next_req) if due[i] < closed_at
                        and not (i in finished and last_t[i] <= w0))
        failed = 0
    gap = served_gap(b, cell, reqs, finished, args.seed)
    control = None
    if getattr(args, "control", False):
        control = {"served_gap": served_gap(b, cell, reqs, finished, args.seed, mode="fp8")}
        common.log(f"control (reference in fp8 in the program's place): {control}")
    return dict(attempted=attempted, failed=failed, e2e=e2e,
                numbers={"served_gap": gap}, control=control,
                checks=[("served_gap", gap, st["limits"]["served_gap"])],
                memory_peak_bytes=peak, ctx=ctx)


def sample(finished: dict, seed: int, want_tokens: int, most: int) -> list[int]:
    """The finished request with most tokens, then others in an order drawn
    from the seed, until ``want_tokens`` served tokens or ``most`` requests."""
    uids = sorted(finished)
    if not uids:
        return []
    longest = max(uids, key=lambda u: (len(finished[u].tokens), -u))
    rest = [u for u in traffic_lib.rng(seed, 5).permutation(uids) if u != longest]
    out, total = [longest], len(finished[longest].tokens)
    for u in rest:
        if total >= want_tokens or len(out) >= most:
            break
        out.append(int(u))
        total += len(finished[u].tokens)
    return out


def reference_logits(b, cell, prompt, served, mode):
    """Reference logits at every position that chose a served token: the
    prompt and the served tokens but the last, padded to one fixed length
    (causal, so padding changes no earlier row)."""
    st = cell["settings"]
    ref, cfg = b["ref"], b["cfg"]
    L = st["reference_len"]
    n_out = st["reference_rows"]
    seq = np.zeros(L, np.int32)
    toks = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    seq[:len(toks)] = toks
    rows = np.zeros(n_out, np.int32)
    rows[:len(served)] = len(prompt) - 1 + np.arange(len(served))
    fn = b.setdefault(("logits", mode), jax.jit(
        lambda w, t, r: ref.logits_at(cfg, w, t, r, mode)))
    return fn(b["weights"], jnp.asarray(seq), jnp.asarray(rows))[:len(served)]


def served_gap(b, cell, reqs, finished, seed, mode=None) -> float:
    """Widest gap, over the sampled requests' served tokens, by which a
    served token's reference logit lies below the reference's best. With
    ``mode`` set (the control), the token is instead the one that mode's
    logits put first at the same position."""
    st = cell["settings"]
    b["weights"] = jax.jit(lambda k: b["ref"].init_weights(
        b["cfg"], k, b["dtype"]))(b["key"])
    picked = sample(finished, seed, st["sample_tokens"], st["sample_most"])
    if not picked:
        common.log("no request finished: nothing to compare")
        return math.inf
    worst = 0.0
    for uid in picked:
        served = finished[uid].tokens
        lg = reference_logits(b, cell, reqs[uid].prompt, served, "f32")
        if mode is None:
            pick = jnp.asarray(served, jnp.int32)
        else:
            pick = jnp.argmax(reference_logits(b, cell, reqs[uid].prompt, served, mode), -1)
        gaps = jnp.max(lg, -1) - jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]
        worst = max(worst, float(jnp.max(gaps)))
    return worst
