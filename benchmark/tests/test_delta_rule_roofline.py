"""The reader of the delta rule's share of its roofline (PR 39) on
hand-made runs: a trace with the two kernels gives the share worked by
hand; a trace without them, a run without a trace or a chip, and a
configuration without linear-attention layers give nothing."""

import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _read(run: dict):
    spec = importlib.util.spec_from_file_location(
        "olmo_delta_rule_roofline", os.path.join(
            ROOT, "benchmark", "metrics", "olmo_delta_rule_roofline.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(run)


def _config() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmo-hybrid-7b.json")) as fh:
        return json.load(fh)


PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
TRAFFIC = {"seq_len": 4096, "n_train": 16, "n_valid": 4, "n_test": 0}
OPS = {"pallas_delta_rule_fwd.3 f32[1,30,4096,192]": {"total_s": 0.3,
                                                      "count": 90},
       "pallas_delta_rule_bwd.1 f32[1,30,4096,96]": {"total_s": 0.5,
                                                     "count": 30},
       "fusion.12 kOutput f32[4096,11008]": {"total_s": 9.0, "count": 60}}


def _run(ops=OPS, cfg=None, peaks=PEAKS, planes=1) -> dict:
    return {"trace": {"ops_s": ops, "train_steps": 30, "planes": planes},
            "config": cfg or _config(), "traffic": TRAFFIC, "batch": 1,
            "peaks": peaks}


def test_the_share_is_the_one_worked_by_hand():
    # three linear layers of 30 heads, keys of 96, values of 192, 4,096
    # tokens: 7 H K V operations a token forward, twice that backward;
    # float32 q, k, v and o forward (2 x 96 + 2 x 192 channels), q, k, v,
    # do and dq, dk, dv backward (4 x 96 + 3 x 192)
    ops = 3 * 4096 * 7.0 * 30 * 96 * 192
    fwd_bytes = 3 * 4096 * 30 * 4.0 * (2 * 96 + 2 * 192)
    bwd_bytes = 3 * 4096 * 30 * 4.0 * (4 * 96 + 3 * 192)
    fwd = max(ops / 197e12, fwd_bytes / 819e9)        # bytes bound it
    bwd = max(2 * ops / 197e12, bwd_bytes / 819e9)
    assert fwd == fwd_bytes / 819e9 and bwd == bwd_bytes / 819e9
    # 30 trained rows; an epoch of 16 evaluates its tail row and the 4
    # validation rows: 30 / 16 x 5 evaluated rows
    need = 30 * (fwd + bwd) + 30 / 16 * 5 * fwd
    assert _read(_run()) == pytest.approx(100.0 * need / 0.8, rel=1e-12)
    # the kernels' time is a plane's: four planes ran four times the time
    assert _read(_run(ops={k: {**v, "total_s": 4 * v["total_s"]}
                           for k, v in OPS.items()}, planes=4)) \
        == pytest.approx(_read(_run()), rel=1e-12)


@pytest.mark.parametrize("case", ["no kernels", "no trace", "no chip",
                                  "other family"])
def test_nothing_to_read_reads_none(case):
    run = _run()
    if case == "no kernels":
        run = _run(ops={k: v for k, v in OPS.items()
                        if not k.startswith("pallas_delta_rule")})
    elif case == "no trace":
        run["trace"] = None
    elif case == "no chip":
        run["peaks"] = None
    else:
        cfg = {k: v for k, v in _config().items()
               if not k.startswith("linear_")}
        run = _run(cfg=cfg)
    assert _read(run) is None
