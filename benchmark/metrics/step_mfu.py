"""Operations the window's work needs (training images at the training
count, evaluated images at the forward count, both from the
configuration's layer list) over window x chips x the published bf16
peak."""


def read(run: dict):
    if run["peaks"] is None:
        return None
    f, w = run["flops"], run["window"]
    need = (w["train_rows"] * f["train_step"]
            + w["eval_rows"] * f["forward"])
    return 100.0 * need / (w["seconds"] * run["chips"]
                           * run["peaks"]["bf16_flops"])
