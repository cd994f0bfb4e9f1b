"""Serving-gateway tests (`launch/serve.py`): priority classes through
`serve_streams`, the async loop at gateway level, the LM monitor demo,
and the CLI — the pieces the CI coverage gate holds at >= 80% for
`repro.launch.serve`.
"""
import numpy as np
import pytest

from repro.launch.serve import _demo_streams, main, serve, serve_streams


def _streams(n, history, live, seed=0, priority=None):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        h = rng.normal(size=(history,)).astype(np.float32)
        lv = rng.normal(size=(live,)).astype(np.float32)
        s = (f"t{i}", h, lv, None)
        if priority is not None:
            s = s + (priority(i),)
        out.append(s)
    return out


def test_serve_streams_priority_classes_and_telemetry():
    res = serve_streams(
        _streams(6, 12, 4, priority=lambda i: "latency" if i % 2
                 else "bulk"),
        backend="scan", buckets=(2, 4), chunk_t=8,
        class_weights={"latency": 4.0, "bulk": 1.0},
        arrivals_per_tick=3)
    assert res["requests"] == 6 and res["samples"] == 6 * 16
    assert set(res["classes"]) == {"latency", "bulk"}
    for cls in ("latency", "bulk"):
        assert res["classes"][cls]["completed"] == 3
        assert "queue_wait_ticks_p95" in res["classes"][cls]
    prios = {rid: pr["priority"] for rid, pr in res["per_request"].items()}
    assert prios["t1"] == "latency" and prios["t0"] == "bulk"
    # decode trickle ticks rode the short cached program
    assert res["short_ticks"] > 0
    assert all(len(key) == 2 for key in res["programs"])


def test_serve_streams_async_matches_sync_flags():
    streams = _streams(4, 10, 6, seed=3)
    streams = [(rid, h, lv * 4.0, 2.0) for rid, h, lv, _ in streams]
    kw = dict(backend="scan", buckets=(2, 4), chunk_t=8, collect=False)
    sync = serve_streams(streams, measure_latency=True, **kw)
    asyn = serve_streams(streams, measure_latency=False, **kw)
    assert sync["flagged"] == asyn["flagged"]
    for rid in sync["per_request"]:
        ps, pa = sync["per_request"][rid], asyn["per_request"][rid]
        assert (ps["samples"], ps["flags"]) == (pa["samples"],
                                                pa["flags"])


def test_serve_streams_rejects_duplicate_rids():
    s = _streams(1, 4, 0)
    with pytest.raises(ValueError, match="duplicate"):
        serve_streams(s + s, backend="scan", buckets=(2,))


def test_lm_serve_demo_tiny():
    """The LM monitor demo end-to-end on a reduced config: prompt
    telemetry replays as chunked prefill, decode telemetry rides the
    adaptive 1-sample lane, flags surface per request."""
    from repro.configs.registry import get_config
    cfg = get_config("llama3.2-1b").reduced()
    res = serve(cfg, batch=2, prompt_len=4, gen=3, backend="scan",
                chunk_t=4)
    assert res["tokens"].shape == (2, 3)
    assert res["monitor"]["ticks"] >= 3
    assert res["monitor"]["completed"] == 2 * 2  # batch x channels
    assert isinstance(res["flagged_requests"], list)
    assert res["prefill_tok_s"] > 0 and res["decode_tok_s"] > 0


def test_cli_streams_mode(capsys, monkeypatch, tmp_path):
    # a set JAX_COMPILATION_CACHE_DIR leaves JAX's cache config alone,
    # so the CLI does not turn the persistent cache on for this worker
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    main(["--mode", "streams", "--requests", "4", "--history", "16",
          "--live", "4", "--backend", "scan"])
    out = capsys.readouterr().out
    assert "[serve]" in out and "decode-short ticks" in out
    assert "class latency" in out and "class bulk" in out


def test_demo_streams_shapes():
    streams = _demo_streams(5, 8, 4)
    assert len(streams) == 5
    rid, h, lv, m, cls = streams[0]
    assert h.shape == (8,) and lv.shape == (4,)
    assert cls == "latency"                # every 4th tenant
    assert streams[1][4] == "bulk"
