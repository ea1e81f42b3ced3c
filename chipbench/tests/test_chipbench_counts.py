"""Operation and byte counts against hand counts of both configurations."""
import json
import pathlib

import pytest

from chipbench import counts

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def _model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("name,params", [("granite-3-2b", 2_533_531_648),
                                         ("h2o-danube-1.8b", 1_831_201_280)])
def test_param_bytes_match_file(name, params):
    m = _model(name)
    assert counts.param_count(m) == params
    assert counts.param_bytes(m) == m["param_bytes"] == 2 * params


def test_granite_decode_reads_its_weights_at_hbm_speed():
    m = _model("granite-3-2b")
    ops, moved = counts.decode(m, 0)
    # the first token reads every weight once (tied table as the head)
    # plus one embedding row read and one cache slot written per layer
    assert moved == 5_067_063_296 + 2 * 2048 + 40 * 2 * 2 * 8 * 64
    t, bound = counts.seconds_at_peak(ops, moved, "TPU v5 lite")
    assert bound == "memory"
    assert t == pytest.approx(6.187e-3, rel=1e-3)


def test_danube_prefill_hand_count():
    m = _model("h2o-danube-1.8b")
    per_layer = 2560 * 2560 * 2 + 2 * 2560 * 640 + 3 * 2560 * 6912
    # 4096 queries, window 4096: query i sees i + 1 keys
    keys = 4096 * 4097 // 2
    want = 24 * (2 * per_layer * 4096 + 4 * 32 * 80 * keys) + 2 * 2560 * 32000
    ops, _ = counts.prefill(m, 4096)
    assert ops == want
    t, bound = counts.seconds_at_peak(*counts.prefill(m, 4096), "TPU v5 lite")
    assert bound == "compute" and t == pytest.approx(want / 197e12)


def test_window_caps_decode_keys():
    m = _model("h2o-danube-1.8b")
    # past the window, each step reads the same 4096 keys: equal work
    assert counts.decode(m, 4096) == counts.decode(m, 5000)
    assert counts.decode(m, 100)[1] < counts.decode(m, 4096)[1]
    kv = 24 * 2 * 8 * 80 * 2
    assert counts.decode(m, 4095)[1] - counts.decode(m, 4094)[1] == kv
    # at the window: every weight but the (untied) embedding table once,
    # one embedding row, 4095 cached keys and values read and one slot
    # written per layer
    ops, moved = counts.decode(m, 4096)
    assert moved == 3_662_402_560 - 2 * 32000 * 2560 + 2 * 2560 + 4096 * kv
    t, bound = counts.seconds_at_peak(ops, moved, "TPU v5 lite")
    assert bound == "memory" and t == pytest.approx(moved / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        counts.peaks("cpu")
