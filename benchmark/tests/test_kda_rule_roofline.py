"""The reader of Kimi delta attention's rule's share of its roofline on
hand-made runs: a trace with the two kernels gives the share worked by
hand; a trace without them, a run without a trace or a chip, a run that
trained no row, and a configuration of another family give nothing."""

import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _read(run: dict):
    spec = importlib.util.spec_from_file_location(
        "solar_kda_rule_roofline", os.path.join(
            ROOT, "benchmark", "metrics", "solar_kda_rule_roofline.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(run)


def _config(name: str = "solar-open2-250b") -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{name}.json")) as fh:
        return json.load(fh)


PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
TRAFFIC = {"seq_len": 4096, "n_train": 16, "n_valid": 4, "n_test": 0}
OPS = {"pallas_kda_rule_fwd.3 f32[1,16,4096,128]": {"total_s": 0.2,
                                                    "count": 90},
       "pallas_kda_rule_bwd.1 f32[1,16,4096,128]": {"total_s": 0.4,
                                                    "count": 30},
       "pallas_delta_rule_bwd.2 f32[1,16,4096,128]": {"total_s": 5.0,
                                                      "count": 30},
       "fusion.12 kOutput f32[4096,24576]": {"total_s": 9.0, "count": 60}}


def _run(ops=OPS, cfg=None, peaks=PEAKS, planes=1, steps=30) -> dict:
    return {"trace": {"ops_s": ops, "train_steps": steps, "planes": planes},
            "config": cfg or _config(), "traffic": TRAFFIC, "batch": 1,
            "peaks": peaks}


def test_the_share_is_the_one_worked_by_hand():
    # three KDA layers of 16 heads, keys and values of 128, 4,096 tokens:
    # 7 H K V operations a token forward, twice that backward; float32 q,
    # k, the running sums, v and o forward (3 x 128 + 2 x 128 channels);
    # q, k, the running sums, v, do and dq, dk, dc, dv backward (6 x 128 +
    # 3 x 128)
    ops = 3 * 4096 * 7.0 * 16 * 128 * 128
    fwd_bytes = 3 * 4096 * 16 * 4.0 * (3 * 128 + 2 * 128)
    bwd_bytes = 3 * 4096 * 16 * 4.0 * (6 * 128 + 3 * 128)
    fwd = max(ops / 197e12, fwd_bytes / 819e9)        # bytes bound it
    bwd = max(2 * ops / 197e12, bwd_bytes / 819e9)
    assert fwd == fwd_bytes / 819e9 and bwd == bwd_bytes / 819e9
    assert 0.61e-3 < fwd < 0.62e-3 and 1.10e-3 < bwd < 1.11e-3
    # 30 trained rows; an epoch of 16 evaluates its tail row and the 4
    # validation rows: 30 / 16 x 5 evaluated rows; the scalar rule's
    # kernel is not this rule's
    need = 30 * (fwd + bwd) + 30 / 16 * 5 * fwd
    assert _read(_run()) == pytest.approx(100.0 * need / 0.6, rel=1e-12)
    # the kernels' time is a plane's: four planes ran four times the time
    assert _read(_run(ops={k: {**v, "total_s": 4 * v["total_s"]}
                           for k, v in OPS.items()}, planes=4)) \
        == pytest.approx(_read(_run()), rel=1e-12)


@pytest.mark.parametrize("case", ["no kernels", "no trace", "no chip",
                                  "no trained row", "other family"])
def test_nothing_to_read_reads_none(case):
    run = _run()
    if case == "no kernels":
        run = _run(ops={k: v for k, v in OPS.items()
                        if not k.startswith("pallas_kda_rule")})
    elif case == "no trace":
        run["trace"] = None
    elif case == "no chip":
        run["peaks"] = None
    elif case == "no trained row":
        run = _run(steps=0)
    else:
        run = _run(cfg=_config("olmo-hybrid-7b"))
    assert _read(run) is None
