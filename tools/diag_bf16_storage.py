"""Isolate the bf16-storage compile failure (the fused step with
``storage_dtype="bfloat16"`` failed to compile on chip on 2026-07-31 while
every f32 variant compiled; ROADMAP Speed 4).  Compiles each Pallas
kernel family at the real AlexNet pair geometries with bf16 inputs, one
at a time, printing PASS/FAIL per family so the first failing compile
names the kernel instead of the whole fused step.

Run ON the chip: python tools/diag_bf16_storage.py
(--tiny: small shapes, for signature/CI validation in interpret mode)
"""
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

if "--tiny" in sys.argv:
    # CI/signature validation off-chip needs interpret-mode Pallas —
    # tuning._INTERPRET reads the env at import, so set it BEFORE any
    # znicz_tpu import or every case FAILs with a Pallas-unsupported
    # error on CPU (ADVICE r4)
    os.environ["ZNICZ_TPU_PALLAS_INTERPRET"] = "1"
    os.environ["JAX_PLATFORMS"] = "cpu"


def main():
    import jax.numpy as jnp
    from znicz_tpu.ops import elementwise, lrn_pool, matmul, pooling

    rng = np.random.default_rng(7)
    tiny = "--tiny" in sys.argv

    def bf16(*s):
        return jnp.asarray(rng.standard_normal(s), jnp.bfloat16)

    cases = []

    # the two AlexNet pair geometries, bf16 storage
    pair_shapes = ([(2, 7, 7, 8)] if tiny
                   else [(128, 55, 55, 96), (128, 27, 27, 256)])
    for shape in pair_shapes:
        x = bf16(*shape)
        xe, xo = lrn_pool.split_cols(x)

        def pair_fwd(xe=xe, xo=xo):
            y, idx = lrn_pool.pallas_lrn_maxpool_split(
                xe, xo, 5, 1e-4, 0.75, 2.0, (3, 3), (2, 2), 0)
            y.block_until_ready()
            return y, idx

        def pair_bwd(xe=xe, xo=xo):
            y, idx = lrn_pool.pallas_lrn_maxpool_split(
                xe, xo, 5, 1e-4, 0.75, 2.0, (3, 3), (2, 2), 0)
            dx = lrn_pool.pallas_gd_lrn_maxpool_split(
                y * jnp.bfloat16(0.1), idx, xe, xo, 5, 1e-4, 0.75,
                2.0, (3, 3), (2, 2), 0, fold_act="strict_relu")
            return dx.block_until_ready()

        cases.append((f"lrn_pool fwd {shape}", pair_fwd))
        cases.append((f"lrn_pool bwd+fold {shape}", pair_bwd))

    x2 = bf16(8, 32) if tiny else bf16(128, 4096)
    cases.append(("act fwd relu bf16",
                  lambda: elementwise.pallas_act_fwd(
                      "relu", x2).block_until_ready()))
    cases.append(("act bwd tanh bf16",
                  lambda: elementwise.pallas_act_bwd(
                      "tanh", x2, x2).block_until_ready()))
    cases.append(("dropout bf16",
                  lambda: elementwise.pallas_dropout(
                      x2, 1234, (0, 0, 0), 0.5)[0].block_until_ready()))
    a, b = ((bf16(16, 32), bf16(32, 24)) if tiny
            else (bf16(512, 9216), bf16(9216, 4096)))
    cases.append(("matmul bf16",
                  lambda: matmul.pallas_matmul(a, b).block_until_ready()))
    xp_ = bf16(2, 7, 7, 8) if tiny else bf16(128, 27, 27, 256)
    cases.append(("pool_select bf16",
                  lambda: pooling.max_pooling(
                      xp_, (3, 3), (2, 2), 0)[0].block_until_ready()))

    failed = 0
    for name, thunk in cases:
        try:
            thunk()
            print(f"PASS {name}")
        except Exception as e:
            failed += 1
            print(f"FAIL {name}: {e!r}"[:2000])
            traceback.print_exc(limit=2)
    print(f"{len(cases) - failed}/{len(cases)} pass")
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
