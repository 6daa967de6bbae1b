#!/usr/bin/env python3
"""Time the decoder's kernels alone on the chip, at the published shapes
of ``mellum2-12b-a2.5b``: splash attention (sliding and full; the fused
backward at three key-block sizes against the two-kernel backward, each
a kernel this tool builds with its own block sizes) and the grouped
expert product (the program's megablox ``gmm``/``tgmm`` and the shipped
one at two other row tiles against ``jax.lax.ragged_dot``), forward and forward + backward.  One
JSON line a case; exits 3 off-TPU.

    chiprun -- python3 tools/bench_decoder_kernels.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

T, HEADS, KV, HD, WINDOW = 8192, 32, 4, 128, 1024
# the usual length of a chunk's sorted buffer: 1.5 x a quarter of the pairs
D, F, HELD, ROWS = 2304, 896, 16, 12288


def timed(fn, *args, reps=5):
    import jax
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def main() -> int:
    import jax
    import jax.numpy as jnp
    if jax.default_backend() != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    from znicz_tpu.ops import attention, moe
    ks = jax.random.split(jax.random.key(0), 8)
    q = jax.random.normal(ks[0], (1, T, HEADS, HD), jnp.bfloat16)
    k, v = (jax.random.normal(kk, (1, T, KV, HD), jnp.bfloat16)
            for kk in ks[1:3])
    pairs = {WINDOW: WINDOW * T - WINDOW * (WINDOW - 1) // 2,
             None: T * (T + 1) // 2}
    from jax.experimental.pallas.ops.tpu import splash_attention as sa
    for window in (WINDOW, None):
        mask = sa.MultiHeadMask([
            sa.CausalMask((T, T)) if window is None
            else sa.LocalMask((T, T), (window - 1, 0), 0)] * (HEADS // KV))
        blk = attention.BLOCK_Q
        for fused_bwd, kv_block in ((True, 512), (True, 1024), (True, 2048),
                                    (False, 512)):
            backward = (dict(use_fused_bwd_kernel=True) if fused_bwd
                        else dict(block_q_dq=blk, block_kv_dq=blk))
            kernel = sa.make_splash_mqa_single_device(
                mask, block_sizes=sa.BlockSizes(
                    block_q=blk, block_kv=blk, block_kv_compute=blk,
                    block_q_dkv=blk, block_kv_dkv=kv_block,
                    block_kv_dkv_compute=blk, **backward))

            def splash(q, k, v, kernel=kernel, window=window):
                return attention.splash_attention(q, k, v, window, kernel)
            fwd = jax.jit(splash)
            both = jax.jit(jax.grad(lambda q, k, v, splash=splash: jnp.sum(
                splash(q, k, v).astype(jnp.float32)), (0, 1, 2)))
            need = 4 * HD * HEADS * pairs[window]
            t_f, t_b = timed(fwd, q, k, v), timed(both, q, k, v)
            print(json.dumps({
                "kernel": "splash", "window": window, "fused_bwd": fused_bwd,
                "block_kv_bwd": kv_block, "fwd_ms": 1e3 * t_f,
                "fwd_bwd_ms": 1e3 * t_b,
                "fwd_share_of_peak": need / t_f / 197e12,
                "fwd_bwd_share_of_peak": 3 * need / t_b / 197e12}),
                flush=True)
    xs = jax.random.normal(ks[3], (ROWS, D), jnp.bfloat16)
    w = jax.random.normal(ks[4], (HELD, D, F), jnp.bfloat16) * 0.02
    # a quarter of the pairs held, near even: 512 rows an expert
    sizes = jnp.asarray([500 + 3 * i for i in range(HELD)], jnp.int32)
    need = 2 * D * F * int(jnp.sum(sizes))
    from jax.experimental.pallas.ops.tpu import megablox

    def shipped(tile):
        # the package's own custom_vjp of the kernels moe.py calls, at
        # another row tile
        return lambda xs, w, sizes: megablox.gmm(
            xs, w, sizes, jnp.float32, (tile, moe._tile(D), moe._tile(F)))
    cases = [("ragged_dot", None, moe.xla_grouped_matmul),
             ("megablox", moe.TILE_M, moe.pallas_grouped_matmul)] + [
        ("megablox", tile, shipped(tile)) for tile in (128, 512)]
    for name, tile, impl in cases:
        def product(xs, w, impl=impl):
            return impl(xs, w, sizes)
        fwd = jax.jit(product)
        both = jax.jit(jax.grad(lambda xs, w: jnp.sum(product(xs, w)),
                                (0, 1)))
        t_f, t_b = timed(fwd, xs, w), timed(both, xs, w)
        print(json.dumps({
            "kernel": name, "tile_m": tile, "fwd_ms": 1e3 * t_f,
            "fwd_bwd_ms": 1e3 * t_b,
            "fwd_share_of_peak": need / t_f / 197e12,
            "fwd_bwd_share_of_peak": 3 * need / t_b / 197e12}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
