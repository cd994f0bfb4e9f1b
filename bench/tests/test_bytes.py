"""The kernels' useful-byte models on hand-worked calls, and the peak
table's refusal of an unknown device."""
import pytest

from bench.lib.harness import BENCH, load_module, peak_of

FLEET = {"detectors": ["teda", "rde", "zscore", "hst", "teda-q"],
         "collect": True}
REPLAY = {"collect": False}


def test_ensemble_bytes_by_hand():
    m = load_module(BENCH / "bytes" / "ensemble_scan_kernel.py")
    # state rows: moment fabric 2*8+1 = 17, hst 2*8+1 = 17, teda-q 2
    assert m.state_rows(FLEET["detectors"]) == 36
    # a verdict: x 4 + bitmask 4 + vote 1 + five scores 20 = 29 bytes
    assert m.per_verdict(FLEET) == 29
    # a slot in a call: k and 36 rows, read and written: 2*4*37 = 296
    assert m.per_slot_call(FLEET) == 296
    # one decode tick of 1,000 slots with one sample each
    assert m.useful_bytes(FLEET, 1000, 1000) == 1000 * (29 + 296)
    # a (256, 4) history chunk: 1,024 verdicts, 4 slot calls
    assert m.useful_bytes(FLEET, 1024, 4) == 1024 * 29 + 4 * 296
    # moment members alone carry the fabric only (17 rows)
    assert m.state_rows(["teda", "rde", "zscore"]) == 17


def test_teda_q_bytes_by_hand():
    m = load_module(BENCH / "bytes" / "teda_q_scan_kernel.py")
    # a verdict: the int32 sample and the int8 flag
    assert m.per_verdict(REPLAY) == 5
    # with eccentricities delivered too
    assert m.per_verdict({"collect": True}) == 9
    # a slot in a call: k, mean, var (int32), read and written
    assert m.per_slot_call(REPLAY) == 24
    # a full (4096, 256) frame
    assert m.useful_bytes(REPLAY, 4096 * 256, 256) == \
        4096 * 256 * 5 + 256 * 24


def test_peak_of_v5e():
    assert peak_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no entry"):
        peak_of("cpu")
