#!/usr/bin/env python3
"""Chip smoke: the detection gateway end to end on a TPU.

Drives `repro.launch.serve.serve_streams` -- scheduler, slot pool,
engine, Pallas kernels -- once per Pallas backend, in this one process:
"pallas", "pallas-q" at Q11.20 and the five-member "ensemble".  The
fleet is a univariate metric fleet in the shape of Yahoo S5 / NAB:
4,096 tenant streams, each a 2,048-sample history replayed as chunked
prefill plus 256 live samples fed one per tick, with about 2% injected
spikes, generated from `--seed`.  Every tenant's verdicts are checked
against a plain reference run over its whole stream.

    python chip_smoke.py                # one chip, the three backends
    python chip_smoke.py --four-chips   # 4-shard gateway vs one chip

The script fails, and prints no result, when JAX finds no TPU.  The
times it prints are a smoke, not a measurement.  The last line of
stdout is {"ok": true, "device": {"platform", "kind", "count"}}.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

FLEET = dict(n=4096, history=2048, live=256)
SPIKE_RATE = 0.02
BUCKETS = (1024, 2048, 4096)
CHUNK_T = 256
M = 3.0
ALL_MEMBERS = ("teda", "rde", "zscore", "hst", "teda-q")
# float path vs the float64 reference: the kernel carries a float32
# running sum and variance recursion over 2,304 samples, so its
# eccentricity drifts from float64 by a relative error that grows at
# worst as k * 2^-24 (1.4e-4 at k = 2,304; the TPU's float32 divide is
# not correctly rounded either)
ECC_RTOL = 1e-3
# a flag may flip only where the float64 statistic sits within that
# drift of the threshold; the fleet's spikes sit far from it
MIN_FLAG_AGREEMENT = 0.9999
# moment-member ensemble scores: the member conformance tolerance
# (`s2/k - mean^2` cancels at small k, tests/test_spec.py)
SCORE_RTOL = SCORE_ATOL = 5e-3


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def make_fleet(n: int, history: int, live: int, seed: int):
    """(T, n) float32 tenant streams: a per-tenant level and scale,
    Gaussian noise, and ~SPIKE_RATE spikes of 6-10 scales."""
    rng = np.random.default_rng(seed)
    t = history + live
    level = rng.normal(0.0, 1.0, size=(1, n)).astype(np.float32)
    scale = rng.uniform(0.5, 1.5, size=(1, n)).astype(np.float32)
    x = level + scale * rng.standard_normal((t, n), dtype=np.float32)
    spikes = rng.random((t, n)) < SPIKE_RATE
    size = rng.uniform(6.0, 10.0, size=(t, n)).astype(np.float32)
    sign = np.where(rng.random((t, n)) < 0.5, -1.0, 1.0).astype(np.float32)
    return np.where(spikes, x + sign * size * scale, x)


def streams_of(x: np.ndarray, history: int):
    return [(f"tenant-{i}", x[:history, i], x[history:, i], None)
            for i in range(x.shape[1])]


def gateway(x, history, backend, *, interpret, **opts):
    """Serve the fleet; returns (result, (T, n) verdict stream, (T, n)
    native ecc / detector-bit stream)."""
    from repro.launch.serve import serve_streams
    n = x.shape[1]
    res = serve_streams(streams_of(x, history), backend=backend,
                        buckets=BUCKETS, chunk_t=CHUNK_T, m=M,
                        interpret=interpret, queue_limit=n, collect=True,
                        **opts)
    sched = res["_scheduler"]
    outs = [sched.results(f"tenant-{i}") for i in range(n)]
    outlier = np.stack([o["outlier"] for o in outs], axis=1)
    ecc = np.stack([o["ecc"] for o in outs], axis=1)
    if outlier.shape != x.shape:
        fail(f"{backend}: verdicts {outlier.shape} for streams {x.shape}")
    return res, outlier, ecc


def engines(pool):
    """Every cached bucket engine of a single or sharded pool, with the
    shard it belongs to."""
    pools = getattr(pool, "pools", [pool])
    return [(s, eng) for s, p in enumerate(pools)
            for eng in p._engines.values()]


def check_placement(pool, devices) -> None:
    """Every engine's state lives on its shard's device."""
    for s, eng in engines(pool):
        st = eng.state
        for name, arr in zip(st._fields, st):
            if arr is not None and arr.devices() != {devices[s]}:
                fail(f"shard {s} engine {eng.name} {name} is on "
                     f"{arr.devices()}, not {devices[s]}")


def check_pallas(x, outlier, ecc) -> str:
    from repro.kernels.ref import teda_ref
    ref = teda_ref(x.astype(np.float64), m=M)
    rel = np.abs(ecc - ref["ecc"]) / np.abs(ref["ecc"])
    if rel.max() > ECC_RTOL:
        fail(f"pallas ecc relative error {rel.max():.3g} > {ECC_RTOL}")
    agree = (outlier == ref["outlier"]).mean()
    if agree < MIN_FLAG_AGREEMENT:
        fail(f"pallas flag agreement {agree} < {MIN_FLAG_AGREEMENT}")
    near = np.abs(ref["zeta"] - ref["threshold"]) <= \
        ECC_RTOL * ref["zeta"]
    if (outlier != ref["outlier"])[~near].any():
        fail("pallas flags differ away from the threshold")
    return (f"ecc max rel err {rel.max():.3g}, flags agree {agree:.6f}, "
            f"{int(ref['outlier'].sum())} reference flags")


def check_pallas_q(x, outlier, ecc, fmt) -> str:
    import jax.numpy as jnp
    from repro.fixedpoint.teda_q import teda_q_scan_chan
    _, ref = teda_q_scan_chan(jnp.asarray(x), fmt, m=M)
    if not np.array_equal(ecc, np.asarray(ref["ecc"])):
        fail("pallas-q ecc is not bit-exact with teda_q_scan_chan")
    if not np.array_equal(outlier, np.asarray(ref["outlier"])):
        fail("pallas-q flags are not bit-exact with teda_q_scan_chan")
    return f"bit-exact, {int(outlier.sum())} flags"


def check_ensemble(x, res, vote, bits, fmt) -> str:
    import jax.numpy as jnp
    from repro.detectors import MOMENT_MEMBERS
    from repro.detectors.ensemble import ensemble_ref
    ref = ensemble_ref(jnp.asarray(x), M, detectors=ALL_MEMBERS, fmt=fmt)
    rbits = np.asarray(ref["det_flags"])
    notes = []
    for d, name in enumerate(ALL_MEMBERS):
        got, exp = (bits >> d) & 1, (rbits >> d) & 1
        if name in MOMENT_MEMBERS:
            agree = (got == exp).mean()
            if agree < MIN_FLAG_AGREEMENT:
                fail(f"ensemble {name} flag agreement {agree}")
            notes.append(f"{name} {agree:.6f}")
        elif not np.array_equal(got, exp):
            fail(f"ensemble {name} flags are not bit-exact")
        # per-tenant mean score over the stream, as the gateway reports
        got_s = np.asarray([res["per_request"][f"tenant-{i}"]
                            ["det_scores"][name]
                            for i in range(x.shape[1])])
        exp_s = np.asarray(ref["per_score"][name]).mean(axis=0)
        # hst / teda-q scores are exact per sample (their flags are
        # checked bit for bit); their means differ by summation order
        tol = ((SCORE_RTOL, SCORE_ATOL) if name in MOMENT_MEMBERS
               else (1e-5, 1e-6))
        if not np.allclose(got_s, exp_s, rtol=tol[0], atol=tol[1]):
            fail(f"ensemble {name} mean scores differ from the oracle")
    agree = (vote == np.asarray(ref["vote"])).mean()
    if agree < MIN_FLAG_AGREEMENT:
        fail(f"ensemble vote agreement {agree}")
    return (f"hst/teda-q bit-exact, moment flag agreement "
            f"{', '.join(notes)}, vote {agree:.6f}")


def report(backend, dev, n_dev, res, watch, mark, note) -> None:
    c = watch.since(mark)
    print(f"[smoke] {backend}: platform={dev.platform} "
          f"kind={dev.device_kind} devices={n_dev} "
          f"samples={res['samples']} ticks={res['ticks']} "
          f"compile_s={c['compile_s']:.1f} compiles={c['compiles']} "
          f"cache_hits={c['cache_hits']} wall_s={res['wall_s']:.1f} "
          f"programs={len(res['programs'])} -- {note} "
          f"(a smoke, not a measurement)", flush=True)


def one_chip(x, history, dev, watch, *, interpret) -> None:
    from repro.fixedpoint import QFormat
    fmt = QFormat(32, 20)
    phases = (
        ("pallas", {}, lambda r, o, e: check_pallas(x, o, e)),
        ("pallas-q", {"fmt": fmt},
         lambda r, o, e: check_pallas_q(x, o, e, fmt)),
        ("ensemble", {"fmt": fmt, "detectors": ALL_MEMBERS},
         lambda r, o, e: check_ensemble(x, r, o, e, fmt)),
    )
    for backend, opts, check in phases:
        mark = watch.mark()
        res, outlier, ecc = gateway(x, history, backend,
                                    interpret=interpret, **opts)
        check_placement(res["_scheduler"].pool, [dev])
        report(backend, dev, 1, res, watch, mark,
               check(res, outlier, ecc))


def four_chips(x, history, devs, watch, *, interpret) -> None:
    """pallas-q over 4 shards, one per chip, with live migration,
    bit-exact against one pool pinned to chip 0."""
    from repro.fixedpoint import QFormat
    fmt = QFormat(32, 20)
    mark = watch.mark()
    one, o1, e1 = gateway(x, history, "pallas-q", interpret=interpret,
                          fmt=fmt, device=devs[0])
    check_placement(one["_scheduler"].pool, devs[:1])
    report("pallas-q shards=1", devs[0], 1, one, watch, mark,
           f"{int(o1.sum())} flags")
    mark = watch.mark()
    four, o4, e4 = gateway(x, history, "pallas-q", interpret=interpret,
                           fmt=fmt, shards=4, shard_devices=devs[:4],
                           rebalance_every=8)
    check_placement(four["_scheduler"].pool, devs[:4])
    if not four["migrations"]:
        fail("the 4-shard run migrated no stream across chips")
    if not (np.array_equal(o1, o4) and np.array_equal(e1, e4)):
        fail("4-shard gateway differs from the one-chip gateway")
    report("pallas-q shards=4", devs[0], 4, four, watch, mark,
           f"bit-exact with shards=1, {four['migrations']} migrations, "
           f"shard occupancy {four['pool']['shard_occupancy']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-shard pallas-q gateway and its "
                         "one-chip comparison (needs 4 devices)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--streams", type=int, default=FLEET["n"],
                    help="fleet size (default: the full 4,096)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache
    from repro.obs import compile_watch
    import jax
    cache = use_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is {dev.platform}",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        print(f"chip_smoke: needs {need} chips, JAX sees {len(devs)}",
              file=sys.stderr)
        return 1
    print(f"[smoke] compile cache {cache}", flush=True)
    watch = compile_watch()
    history = FLEET["history"]
    x = make_fleet(args.streams, history, FLEET["live"], args.seed)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(x, history, devs, watch, interpret=False)
    else:
        one_chip(x, history, dev, watch, interpret=False)
    print(f"[smoke] all phases passed in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
