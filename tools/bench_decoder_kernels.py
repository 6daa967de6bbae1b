#!/usr/bin/env python3
"""Time the decoder's kernels alone on the chip, at the published shapes
of ``mellum2-12b-a2.5b``: splash attention (sliding and full; the fused
backward at three key-block sizes against the two-kernel backward, each
a kernel this tool builds with its own block sizes) and the grouped
expert product (the program's megablox ``gmm``/``tgmm`` and the shipped
one at two other row tiles against ``jax.lax.ragged_dot``), forward and forward + backward; and the
expert layer of one chunk alone (``ops/moe.held_expert_sum``: sort, rows
there, three products, rows back) with its three products' time beside
it, so that what the layer spends outside its kernels can be read
without the step.  One JSON line a case; exits 3 off-TPU.

    chiprun -- python3 tools/bench_decoder_kernels.py [splash] [gmm] [layer]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

T, HEADS, KV, HD, WINDOW = 8192, 32, 4, 128, 1024
D, F, HELD, EXPERTS, TOP_K = 2304, 896, 16, 64, 8


def timed(fn, *args, reps=5):
    import jax
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def main(argv) -> int:
    import jax
    if jax.default_backend() != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    ks = jax.random.split(jax.random.key(0), 8)
    cases = {"splash": splash, "gmm": grouped_products,
             "layer": expert_layer}
    for name in argv or list(cases):
        cases[name](ks)
    return 0


def splash(ks) -> None:
    import jax
    import jax.numpy as jnp
    from znicz_tpu.ops import attention
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


def grouped_products(ks) -> None:
    import jax
    import jax.numpy as jnp
    from znicz_tpu.ops import moe
    # the first piece of a chunk's sorted pairs, as the program takes it
    rows = moe.piece_rows(moe.CHUNK_TOKENS * TOP_K, HELD / EXPERTS)[0]
    xs = jax.random.normal(ks[3], (rows, D), jnp.bfloat16)
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


def expert_layer(keys) -> None:
    """One chunk of ``CHUNK_TOKENS`` tokens through ``held_expert_sum`` at
    a fresh router's routing (8 of 64 a token, 16 held), and the layer's
    three grouped products alone at the same group sizes."""
    import jax
    import jax.numpy as jnp
    from znicz_tpu.ops import moe
    n = moe.CHUNK_TOKENS
    ks = jax.random.split(keys[5], 6)
    xn = jax.random.normal(ks[0], (n, D), jnp.float32)
    _, experts = jax.lax.top_k(jax.random.normal(ks[1], (n, EXPERTS)), TOP_K)
    weights = jax.random.uniform(ks[2], (n, TOP_K), minval=0.05, maxval=0.3)
    wg, wu = (jax.random.normal(k, (HELD, D, F)) * 0.02 for k in ks[3:5])
    wd = jax.random.normal(ks[5], (HELD, F, D)) * 0.02

    def layer(xn, weights, wg, wu, wd):
        return moe.held_expert_sum(xn, weights, experts, wg, wu, wd, 0,
                                   jnp.bfloat16, HELD / EXPERTS)
    out, counts, moved = jax.jit(layer)(xn, weights, wg, wu, wd)
    rows = int(moved)
    xs = jax.random.normal(ks[0], (rows, D), jnp.bfloat16)

    def products(xs, wg, wu, wd):
        hidden = (jax.nn.silu(moe.grouped_matmul(xs, wg, counts))
                  * moe.grouped_matmul(xs, wu, counts)).astype(xs.dtype)
        return moe.grouped_matmul(hidden, wd, counts)
    half = tuple(w.astype(jnp.bfloat16) for w in (wg, wu, wd))
    times = {}
    for name, fn, args in (("layer", lambda *a: layer(*a)[0],
                            (xn, weights, wg, wu, wd)),
                           ("products", products, (xs,) + half)):
        grad = jax.grad(lambda *a, fn=fn: jnp.sum(fn(*a) ** 2),
                        tuple(range(len(args))))
        times[name] = (1e3 * timed(jax.jit(fn), *args, reps=20),
                       1e3 * timed(jax.jit(grad), *args, reps=20))
    print(json.dumps({
        "kernel": "expert_layer", "tokens": n, "pairs_held": int(counts.sum()),
        "rows_moved": rows, "fwd_ms": times["layer"][0],
        "fwd_bwd_ms": times["layer"][1],
        "products_fwd_ms": times["products"][0],
        "products_fwd_bwd_ms": times["products"][1],
        "outside_products_fwd_ms": times["layer"][0] - times["products"][0],
        "outside_products_fwd_bwd_ms": (times["layer"][1]
                                        - times["products"][1])}),
        flush=True)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
