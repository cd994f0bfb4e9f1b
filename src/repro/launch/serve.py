"""Serving gateway: continuous-batching TEDA detection + LM monitoring.

Two entry points, both driven by the `launch/batching.py` scheduler
(admission queue, chunked prefill, per-request telemetry, backpressure
when every capacity bucket is full):

  * `serve_streams` — the generic detection gateway: tenant streams
    (history + live samples, per-tenant sensitivity `m`) arrive on a
    schedule, attach to engine slots, and are served continuously.
    This is the workload driver behind `benchmarks/bench_serving.py`.

        PYTHONPATH=src python -m repro.launch.serve --mode streams \
            --requests 16 --history 256 --live 32 --backend pallas

  * `serve` — the LM demo: prefills a prompt batch, then decodes while
    per-request telemetry (logit entropy, max-logit) streams through
    the detection gateway — prompt-phase telemetry replays as chunked
    prefill (the monitor is warmed up on the tenant's own history), and
    decode-phase telemetry rides the per-tick trickle.  Flagged
    requests surface the way a production gateway would quarantine
    degenerate generations.

        PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b \
            --scale tiny --batch 4 --prompt-len 32 --gen 32

The telemetry itself (log-softmax entropy, max-logit) is computed
*inside* the jitted decode step — the Python loop threads device
arrays and hands the host-side scheduler one small (B, 2) array per
generated token.
"""
from __future__ import annotations

import argparse
import functools
import time
from collections import deque
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.launch.batching import BatchingScheduler, Request
from repro.launch.compile_cache import use_compile_cache
from repro.models import init_cache, init_lm_params, lm_decode_step

N_CHANNELS = 2  # per-request telemetry: (entropy, max-logit)


# --------------------------------------------------------------- gateway
def serve_streams(streams: Sequence[tuple],
                  *, backend: str = "scan",
                  buckets: Tuple[int, ...] = (8, 16, 32, 64),
                  chunk_t: int = 32, m: float = 3.0, fmt=None,
                  interpret: Optional[bool] = None,
                  queue_limit: int = 64,
                  arrivals_per_tick: Optional[int] = None,
                  feed_per_tick: int = 1, collect: bool = False,
                  measure_latency: bool = True,
                  max_ticks: int = 1_000_000,
                  registry=None, tracer=None, on_event=None,
                  **engine_opts) -> dict:
    """Serve tenant streams through the continuous-batching scheduler.

    `streams` is a sequence of (rid, history, live, m) or
    (rid, history, live, m, priority) tuples — history replays as
    chunked prefill on admission, live samples are fed `feed_per_tick`
    per tick (the decode trickle), `m` is the tenant's sensitivity
    (None: the gateway default), `priority` its admission class (see
    `BatchingScheduler(class_weights=)`; weights pass through
    `engine_opts`, e.g. `class_weights={"latency": 4, "bulk": 1}`).
    Under `backend="ensemble"` a tuple may extend to
    (rid, history, live, m, priority, detectors, vote) — the tenant's
    detector subset and vote mode, threaded to its slot at admission.
    `arrivals_per_tick` models offered load (None: everything offered
    up front); arrivals the admission queue rejects are re-offered
    next tick, counted in `rejected_submits` — the backpressure
    measure.

    With `measure_latency=False` the scheduler runs its async
    double-buffered loop (host bookkeeping overlapped with device
    compute); True keeps the synchronous loop so per-chunk wall times
    are honest latencies.  `pipeline_depth` (via `engine_opts`) keeps
    up to that many fused calls in flight with slot fencing —
    gateway results stay bit-exact with depth 1, but
    `measure_latency=True` overrides it back to the synchronous loop,
    so depth and honest per-call latencies are mutually exclusive
    knobs.  `block_c` (also via `engine_opts`) tiles the kernel grid's
    channel axis for multi-core TPU scaling at wide capacities.
    `shards=K` (with optional `rebalance_every`) swaps the single pool
    for a `ShardedPool`: consistent-hash routing over K device shards,
    one fused call per shard per tick, live migration under the
    occupancy rebalancer — gateway verdicts stay bit-exact with the
    single pool (see README §sharding).

    Observability (`repro.obs`): `registry`/`tracer` pass through to
    the scheduler (and down to pool + engines); `on_event` is a
    callback receiving each streamed `Event` (admitted /
    chunk_retired / done / evicted) as it retires — the push side of
    `BatchingScheduler.subscribe()`.

    Returns sustained rates, latency percentiles, queue-wait stats,
    per-priority-class telemetry, per-request telemetry, and a
    `metrics` registry snapshot.
    """
    class _Rec:
        __slots__ = ("req", "live", "fed", "closed")

        def __init__(self, rid, history, live, m_req,
                     priority="default", detectors=None, vote=None):
            self.req = Request(rid, np.asarray(history, np.float32),
                               priority=priority,
                               detectors=(None if detectors is None
                                          else tuple(detectors)),
                               vote=vote)
            self.req.m = m_req
            self.live = np.asarray(live, np.float32).reshape(-1)
            self.fed = 0
            self.closed = False

    recs = {s[0]: _Rec(*s) for s in streams}
    if len(recs) != len(streams):
        raise ValueError("duplicate request ids in streams")
    # retention must cover the whole run: every request's telemetry is
    # read back after the drain, so none may be evicted mid-run
    engine_opts["keep_finished"] = max(
        engine_opts.get("keep_finished", 1024), len(recs))
    sched = BatchingScheduler(
        backend, buckets=buckets, chunk_t=chunk_t, m=m, fmt=fmt,
        interpret=interpret, queue_limit=queue_limit, collect=collect,
        measure_latency=measure_latency, registry=registry,
        tracer=tracer, **engine_opts)
    if on_event is not None:
        sched.events.attach(on_event)
    waiting = deque(recs.values())
    total_samples = sum(len(r.req.history) + len(r.live)
                        for r in recs.values())

    t0 = time.perf_counter()
    while sched.completed < len(recs):
        if sched.tick_no >= max_ticks:
            raise RuntimeError(f"serve_streams exceeded {max_ticks} ticks")
        budget = len(waiting) if arrivals_per_tick is None \
            else arrivals_per_tick
        while waiting and budget > 0:
            rec = waiting[0]
            if not sched.submit(rec.req):
                break  # queue full: re-offer this arrival next tick
            waiting.popleft()
            budget -= 1
            if not len(rec.live):
                sched.close(rec.req.rid)
                rec.closed = True
        for rec in recs.values():
            if rec.closed or rec.req.rid not in sched.stats_by_rid:
                continue
            take = min(feed_per_tick, len(rec.live) - rec.fed)
            if take:
                sched.feed(rec.req.rid, rec.live[rec.fed:rec.fed + take])
                rec.fed += take
            if rec.fed == len(rec.live):
                sched.close(rec.req.rid)
                rec.closed = True
        sched.step()
    wall = time.perf_counter() - t0

    agg = sched.stats()
    waits = [sched.telemetry(rid).queue_wait_ticks for rid in recs]
    per_request = {
        rid: {"samples": st.samples, "flags": st.flags,
              "queue_wait_ticks": st.queue_wait_ticks,
              "prefill_chunks": st.prefill_chunks,
              "decode_steps": st.decode_steps, "slot": st.slot,
              "shard": st.shard, "migrations": st.migrations,
              "priority": st.priority,
              "det_flags": dict(st.det_flags),
              # ensemble backend only: per-detector mean score over the
              # request's retired samples (the kernel's float score
              # streams, threaded engine -> pool -> scheduler events)
              "det_scores": {d: s / max(st.samples, 1)
                             for d, s in st.det_scores.items()}}
        for rid, st in ((rid, sched.telemetry(rid)) for rid in recs)}
    return {
        "backend": backend, "chunk_t": chunk_t,
        "requests": len(recs), "samples": total_samples,
        "wall_s": wall, "ticks": agg["ticks"],
        "requests_per_s": len(recs) / wall,
        "samples_per_s": total_samples / wall,
        "rejected_submits": agg["rejected_submits"],
        "chunk_latency": agg["chunk_latency"],
        "short_ticks": agg["short_ticks"],
        "programs": agg["programs"],
        "classes": agg["classes"],
        "queue_wait_ticks_p50": float(np.percentile(waits, 50)),
        "queue_wait_ticks_p95": float(np.percentile(waits, 95)),
        "flagged": sorted(rid for rid in recs
                          if sched.telemetry(rid).flags),
        "pool": agg["pool"],
        # sharded gateway only (shards > 1 via engine_opts)
        **{k: agg[k] for k in ("shards", "migrations", "imbalance")
           if k in agg},
        "per_request": per_request,
        "metrics": sched.registry.snapshot(),
        "_scheduler": sched,  # for tests; stripped by the benchmark
    }


# --------------------------------------------------------------- LM demo
def make_decode_step(cfg, greedy: bool):
    """Build the jitted decode step with fused telemetry extraction.

    Returns the sampled token plus the (B,) entropy / max-logit rows
    the monitor gateway consumes — no extra host round-trip beyond the
    one that feeds the scheduler.
    """

    @functools.partial(jax.jit, donate_argnums=(3,))
    def step(params, tok, pos, caches, key):
        logits, caches = lm_decode_step(params, tok, pos, caches, cfg)
        ent, mx = _telemetry(logits)
        if greedy:
            nxt = jnp.argmax(logits, axis=-1)
        else:
            nxt = jax.random.categorical(jax.random.fold_in(key, pos),
                                         logits)
        return nxt, caches, ent, mx

    return step


@jax.jit
def _telemetry(logits):
    logp = jax.nn.log_softmax(logits, axis=-1)
    ent = -jnp.sum(jnp.exp(logp) * logp, axis=-1)  # (B,)
    mx = jnp.max(logits, axis=-1)                  # (B,)
    return ent, mx


def _monitor_buckets(n_slots: int) -> Tuple[int, ...]:
    """Bucket ladder reaching at least n_slots (powers of two from 8)."""
    ladder = [8]
    while ladder[-1] < n_slots:
        ladder.append(ladder[-1] * 2)
    return tuple(ladder)


def serve(cfg, batch: int, prompt_len: int, gen: int, m: float = 3.5,
          seed: int = 0, greedy: bool = True, backend: str = "scan",
          chunk_t: int = 16, fmt=None):
    assert cfg.family != "encdec", "serve example targets decoder-only LMs"
    key = jax.random.PRNGKey(seed)
    params = init_lm_params(key, cfg)
    prompts = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab)

    max_seq = prompt_len + gen
    caches = init_cache(cfg, batch, max_seq, dtype=jnp.float32)
    decode = jax.jit(
        lambda p, t, pos, c: lm_decode_step(p, t, pos, c, cfg),
        donate_argnums=(3,))
    step = make_decode_step(cfg, greedy)

    # prefill by teacher-forcing the prompt through the decode path,
    # banking per-token telemetry — it becomes the monitor's chunked-
    # prefill history (the gateway warms up on the tenant's own prompt)
    t0 = time.perf_counter()
    prompt_tel = []
    for i in range(prompt_len - 1):
        logits, caches = decode(params, prompts[:, i], jnp.int32(i), caches)
        prompt_tel.append(_telemetry(logits))
    jax.block_until_ready(caches)
    prefill_s = time.perf_counter() - t0
    # (prompt_len-1, B, 2) on host, one request x channel stream each
    # (empty for prompt_len == 1: the monitor starts cold)
    hist = (np.stack([np.stack([np.asarray(e), np.asarray(x)], -1)
                      for e, x in prompt_tel])
            if prompt_tel else np.zeros((0, batch, N_CHANNELS),
                                        np.float32))

    # monitor gateway: one detection request per request x channel,
    # admitted with the prompt history, fed one sample per decoded token
    sched = BatchingScheduler(
        backend, buckets=_monitor_buckets(batch * N_CHANNELS),
        chunk_t=chunk_t, m=m, fmt=fmt,
        queue_limit=batch * N_CHANNELS, collect=True)
    rids = [(b, c) for b in range(batch) for c in range(N_CHANNELS)]

    def rid(b, c):
        return f"req{b}/ch{c}"

    for b, c in rids:
        ok = sched.submit(Request(rid(b, c), hist[:, b, c], m=m))
        assert ok, "monitor queue sized to the request set"

    outs = []
    tok = prompts[:, -1]
    t0 = time.perf_counter()
    for i in range(gen):
        pos = jnp.int32(prompt_len - 1 + i)
        tok, caches, ent, mx = step(params, tok, pos, caches, key)
        outs.append(tok)
        tel = np.stack([np.asarray(ent), np.asarray(mx)], -1)  # (B, 2)
        for b, c in rids:
            sched.feed(rid(b, c), tel[b, c:c + 1])
        sched.step()
    for b, c in rids:
        sched.close(rid(b, c))
    sched.drain()
    toks_out = np.stack([np.asarray(t) for t in outs], axis=1)
    decode_s = time.perf_counter() - t0

    # flag on decode-phase verdicts only (any channel): the prompt is
    # the tenant's own baseline, not the generation under scrutiny
    flagged = [b for b in range(batch)
               if any(sched.results(rid(b, c))["outlier"][-gen:].any()
                      for c in range(N_CHANNELS))]
    return {
        "tokens": toks_out,
        "flagged_requests": flagged,
        "prefill_tok_s": batch * (prompt_len - 1) / prefill_s,
        "decode_tok_s": batch * gen / decode_s,
        "monitor": sched.stats(),
    }


# ------------------------------------------------------------------- CLI
def _demo_streams(n: int, history: int, live: int, seed: int = 0):
    """Synthetic tenant mix: drifting means, one loud anomaly burst,
    every fourth tenant in the latency class (the rest are bulk)."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        h = rng.normal(loc=i * 0.1, size=(history,)).astype(np.float32)
        lv = rng.normal(loc=i * 0.1, size=(live,)).astype(np.float32)
        if live and i % 3 == 0:
            lv[live // 2] += 15.0  # anomaly burst mid-stream
        cls = "latency" if i % 4 == 0 else "bulk"
        out.append((f"tenant-{i}", h, lv, 2.0 + (i % 3), cls))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="lm", choices=["lm", "streams"])
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--scale", default="tiny", choices=["tiny", "full"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--backend", default="scan")
    ap.add_argument("--chunk-t", type=int, default=16)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--history", type=int, default=256)
    ap.add_argument("--live", type=int, default=32)
    ap.add_argument("--arrivals-per-tick", type=int, default=None)
    ap.add_argument("--decode-t", type=int, default=1,
                    help="short program length for decode-only ticks")
    ap.add_argument("--pipeline-depth", type=int, default=1,
                    help="in-flight fused calls (>1 runs the async "
                         "loop: latency measurement switches off)")
    ap.add_argument("--block-c", type=int, default=None,
                    help="channel-block width of the kernel grid "
                         "(multiple of 128; default: one strip)")
    ap.add_argument("--shards", type=int, default=1,
                    help="shard the pool over this many devices "
                         "(consistent-hash routing + live migration)")
    ap.add_argument("--rebalance-every", type=int, default=0,
                    help="run the occupancy rebalancer every N ticks "
                         "(0: never; sharded gateway only)")
    args = ap.parse_args(argv)
    use_compile_cache()

    fmt = None
    if args.backend == "pallas-q":
        from repro.fixedpoint import QFormat
        fmt = QFormat(32, 20)  # the README's Q11.20 reference format

    if args.mode == "streams":
        res = serve_streams(
            _demo_streams(args.requests, args.history, args.live),
            backend=args.backend, chunk_t=args.chunk_t, fmt=fmt,
            decode_t=args.decode_t,
            pipeline_depth=args.pipeline_depth,
            block_c=args.block_c,
            shards=args.shards,
            # one device per shard: the pool raises when the host has
            # fewer devices than shards instead of stacking them on one
            shard_devices=(jax.devices()[:args.shards]
                           if args.shards > 1 else None),
            rebalance_every=args.rebalance_every,
            # depth > 1 only pipelines in the async loop
            measure_latency=args.pipeline_depth <= 1,
            class_weights={"latency": 4.0, "bulk": 1.0},
            arrivals_per_tick=args.arrivals_per_tick)
        lat = res["chunk_latency"]
        print(f"[serve] {res['requests']} requests, "
              f"{res['samples']} samples in {res['wall_s']:.2f}s "
              f"({res['requests_per_s']:.1f} req/s, "
              f"{res['samples_per_s']:.0f} samples/s)")
        print(f"[serve] chunk latency p50 {lat.get('p50_ms', 0):.2f}ms "
              f"p95 {lat.get('p95_ms', 0):.2f}ms, "
              f"queue wait p95 {res['queue_wait_ticks_p95']:.0f} ticks, "
              f"{res['rejected_submits']} backpressured submits, "
              f"{res['short_ticks']} decode-short ticks")
        for cls, c in sorted(res["classes"].items()):
            print(f"[serve]   class {cls}: {c['completed']} done, "
                  f"queue wait p95 "
                  f"{c.get('queue_wait_ticks_p95', 0):.0f} ticks")
        if args.shards > 1:
            print(f"[serve] {res['shards']} shards, "
                  f"{res['migrations']} migrations, "
                  f"final imbalance {res['imbalance']}")
        print(f"[serve] flagged tenants: {res['flagged']}")
        return

    cfg = get_config(args.arch)
    if args.scale == "tiny":
        cfg = cfg.reduced()
    res = serve(cfg, args.batch, args.prompt_len, args.gen,
                backend=args.backend, chunk_t=args.chunk_t, fmt=fmt)
    print(f"[serve] prefill {res['prefill_tok_s']:.1f} tok/s, "
          f"decode {res['decode_tok_s']:.1f} tok/s")
    print(f"[serve] TEDA-flagged requests: {res['flagged_requests']}")
    print(f"[serve] monitor: {res['monitor']['ticks']} ticks, "
          f"pool {res['monitor']['pool']}")
    print(f"[serve] sample continuation (req 0): "
          f"{res['tokens'][0][:16].tolist()}")


if __name__ == "__main__":
    main()
