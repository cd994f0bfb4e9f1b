"""The control, the plain reference one step down in precision put in
the program's place, fails each cell's comparison at its limits, while
the reference itself passes them: at a size a test run holds."""
import numpy as np

from bench.lib.harness import BENCH, Cell, load_module


def _limits(name):
    return Cell(name, staged=True).wl["limits"]


def test_fleet_control_fails_its_limits():
    cell = Cell("fleet-ens5.live", staged=True)
    cfg = cell.cfg
    ref = load_module(BENCH / "reference" / "ensemble.py")
    sig = load_module(BENCH / "signals" / "tenant_series.py")
    hist, live, n = 256, 40, 256
    x = sig.make(cfg["signal"], np.random.default_rng(5), t_len=hist + live,
                 n=n)
    want = ref.expected(x, cfg)
    store = ref.Store(hist + live, n)
    for i in range(n):   # a perfect program: history in one chunk, then
        for pos, k in [(0, hist)] + [(hist + j, 1) for j in range(live)]:
            store.add(i, pos, {
                "n": k, "ecc": want["bits"][pos:pos + k, i],
                "outlier": want["vote"][pos:pos + k, i],
                "det_scores": dict(zip(ref.MEMBERS, want["scores"][
                    :, pos:pos + k, i].sum(axis=1)))})
    lengths = np.full(n, hist + live)
    limits = _limits("fleet-ens5.live")
    numbers, missing = ref.compare(store, lengths, want, limits)
    assert missing == 0
    assert all(v <= lim for v, lim in numbers.values()), numbers
    numbers, _ = ref.compare(ref.control_store(store, x, cfg), lengths,
                             want, limits)
    assert any(v > lim for v, lim in numbers.values()), numbers


def test_replay_control_fails_its_limits():
    cell = Cell("linerate-q.replay")
    cfg = cell.cfg
    ref = load_module(BENCH / "reference" / "teda_q.py")
    sig = load_module(BENCH / "signals" / "vibration.py")
    s = dict(cfg["signal"], recordings=4, length=8192)
    recs = sig.make(s, np.random.default_rng(6), fs=cfg["rate_hz"])
    want = ref.expected(recs, cfg)
    rec_of = lambda key: (key[0] + key[1]) % 4
    store, fed = ref.Store(), {}
    for c in range(8):
        for ps in range(2):
            key = (c, ps)
            fed[key] = 8192
            for pos in range(0, 8192, 4096):
                store.add(key, pos, want[pos:pos + 4096, rec_of(key)])
    limits = _limits("linerate-q.replay")
    numbers, missing = ref.compare(store, fed, want, rec_of, limits)
    assert missing == 0 and numbers["flag_mismatch"][0] == 0
    numbers, _ = ref.compare(ref.control_store(store, recs, cfg, rec_of),
                             fed, want, rec_of, limits)
    assert numbers["flag_mismatch"][0] > limits["flag_mismatch"], numbers
