#!/usr/bin/env python3
"""Time the decoder's kernels alone on the chip, at the published shapes
of ``mellum2-12b-a2.5b``: splash attention (sliding and full; the fused
backward at three key-block sizes against the two-kernel backward, each
a kernel this tool builds with its own block sizes) and the grouped
expert product (the program's megablox ``gmm``/``tgmm`` and the shipped
one at two other row tiles against ``jax.lax.ragged_dot``), forward and forward + backward; and the
expert layer of one chunk alone at the three sparse cells' shapes
(``ops/moe.held_expert_sum``: sort, rows there, three products, rows back)
with its three products' time beside it, so that what the layer spends
outside its kernels can be read without the step; and the way back from
sorted space alone at the same shapes (the gather over every pair against
the segment sum over the live rows); and the gated delta rule at the shapes of one of
``olmo-hybrid-7b``'s linear layers (``ops/gdn.py``: the kernels against
the ``jax.numpy`` chunked form); and Kimi delta attention's per-channel
rule the same way at the shapes of one of ``solar-open2-250b``'s KDA
layers; and the embedding's table gradient at
the four decoder cells' shapes (XLA's scatter-add, alone and into the
update's decay term, against ``ops/attention.embed_grad``'s kernel).
One JSON line a case; exits 3 off-TPU.

    chiprun -- python3 tools/bench_decoder_kernels.py [splash] [gmm] [layer]
        [combine] [delta_rule] [kda_rule] [embed]
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
             "layer": expert_layer, "combine": combine,
             "delta_rule": delta_rule, "kda_rule": kda_rule, "embed": embed}
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


#: the sparse cells' expert layers: tokens a chunk, experts a token, experts
#: of the router, experts held, width, expert width
EXPERT_SHAPES = {"granite-4.0-h-small": (2048, 10, 72, 9, 4096, 768),
                 "solar-open2-250b": (4096, 8, 320, 8, 4096, 1280),
                 "mellum2-12b-a2.5b": (4096, 8, 64, 16, 2304, 896)}


def _routing(key, n, top_k, experts):
    """A fresh router's choices: ``top_k`` distinct experts of ``experts``
    a token, near even, and weights between 0.05 and 0.3."""
    import jax
    ks = jax.random.split(key, 2)
    _, chosen = jax.lax.top_k(jax.random.normal(ks[0], (n, experts)), top_k)
    return (jax.random.uniform(ks[1], (n, top_k), minval=0.05, maxval=0.3),
            chosen)


def expert_layer(keys) -> None:
    """One chunk of each sparse cell's expert layer through
    ``held_expert_sum`` at a fresh router's routing, and the layer's three
    grouped products alone at the same group sizes."""
    import jax
    import jax.numpy as jnp
    from znicz_tpu.ops import moe
    for name, (n, top_k, all_experts, held, d, f) in EXPERT_SHAPES.items():
        ks = jax.random.split(keys[5], 6)
        xn = jax.random.normal(ks[0], (n, d), jnp.float32)
        weights, experts = _routing(ks[1], n, top_k, all_experts)
        wg, wu = (jax.random.normal(k, (held, d, f)) * 0.02 for k in ks[3:5])
        wd = jax.random.normal(ks[5], (held, f, d)) * 0.02

        def layer(xn, weights, wg, wu, wd, experts=experts, held=held,
                  all_experts=all_experts):
            return moe.held_expert_sum(xn, weights, experts, wg, wu, wd, 0,
                                       jnp.bfloat16, held / all_experts)
        out, counts, moved = jax.jit(layer)(xn, weights, wg, wu, wd)
        rows = int(moved)
        xs = jax.random.normal(ks[0], (rows, d), jnp.bfloat16)

        def products(xs, wg, wu, wd, counts=counts):
            hidden = (jax.nn.silu(moe.grouped_matmul(xs, wg, counts))
                      * moe.grouped_matmul(xs, wu, counts)).astype(xs.dtype)
            return moe.grouped_matmul(hidden, wd, counts)
        half = tuple(w.astype(jnp.bfloat16) for w in (wg, wu, wd))
        times = {}
        for part, fn, args in (("layer", lambda *a, layer=layer: layer(*a)[0],
                                (xn, weights, wg, wu, wd)),
                               ("products", products, (xs,) + half)):
            grad = jax.grad(lambda *a, fn=fn: jnp.sum(fn(*a) ** 2),
                            tuple(range(len(args))))
            times[part] = (1e3 * timed(jax.jit(fn), *args, reps=20),
                           1e3 * timed(jax.jit(grad), *args, reps=20))
        print(json.dumps({
            "kernel": "expert_layer", "config": name, "tokens": n,
            "pairs_held": int(counts.sum()), "rows_moved": rows,
            "fwd_ms": times["layer"][0], "fwd_bwd_ms": times["layer"][1],
            "products_fwd_ms": times["products"][0],
            "products_fwd_bwd_ms": times["products"][1],
            "outside_products_fwd_ms": (times["layer"][0]
                                        - times["products"][0]),
            "outside_products_fwd_bwd_ms": (times["layer"][1]
                                            - times["products"][1])}),
            flush=True)


def combine(keys) -> None:
    """The way back from sorted space alone, at each sparse cell's expert
    layer (a chunk's first piece of sorted pairs at a fresh router's
    routing): ``combine``'s forward (float32 rows times their weights) and
    ``dispatch_rows``' gradient (bfloat16 rows), as the gather over every
    token's ``top_k`` rows (``moe._sum_pair_rows``) and, where the program
    has it, as the sorted segment sum over the live rows
    (``moe.pair_sum``); the largest gap to the gather against the largest
    value, and the share of the HBM bound: the live rows read once and the
    ``(tokens, d)`` sum written once, over 819 GB/s."""
    import jax
    import jax.numpy as jnp
    from znicz_tpu.ops import moe
    for name, (n, top_k, all_experts, held, d, _) in EXPERT_SHAPES.items():
        ks = jax.random.split(keys[4], 4)
        weights, experts = _routing(ks[0], n, top_k, all_experts)
        key = jnp.where(experts < held, experts, held).reshape(-1)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        place = jnp.argsort(order).astype(jnp.int32).reshape(n, top_k)
        rows = moe.piece_rows(n * top_k, held / all_experts)[0]
        live = jnp.minimum(jnp.sum(key < held), rows).astype(jnp.int32)
        taken = order[:rows]
        for use, dtype, w in (("combine", jnp.float32, weights),
                              ("dispatch_gradient", jnp.bfloat16, None)):
            src = jax.random.normal(ks[1], (rows, d)).astype(dtype)
            forms = {"gather": jax.jit(
                lambda src, w, row=place: moe._sum_pair_rows(
                    src, row, live, w))}
            if hasattr(moe, "pair_sum"):
                forms["kernel"] = jax.jit(
                    lambda src, w: moe.pair_sum(src, taken, live, n, top_k,
                                                w))
            want = forms["gather"](src, w).astype(jnp.float32)
            item = jnp.dtype(dtype).itemsize
            least = (int(live) + n) * d * item / 819e9
            for form, fn in forms.items():
                got = fn(src, w).astype(jnp.float32)
                t = timed(fn, src, w, reps=20)
                print(json.dumps({
                    "kernel": "way_back", "config": name, "use": use,
                    "form": form, "tokens": n, "top_k": top_k, "rows": rows,
                    "live": int(live), "width": d, "ms": 1e3 * t,
                    "hbm_bound_share": least / t,
                    "gap": float(jnp.max(jnp.abs(got - want))
                                 / jnp.max(jnp.abs(want)))}), flush=True)


def delta_rule(keys) -> None:
    """One row of ``olmo-hybrid-7b``'s linear layer (4,096 tokens, 30 heads,
    keys of 96, values of 192, chunks of 64, bfloat16 operands): forward,
    and forward + backward (the gradient of all five inputs), through the
    kernels and through the chunked form; the largest gap of the kernels'
    output and gradients to the chunked form's, against their largest
    value; and the share of the rule's roofline
    (``benchmark/metrics/olmo_delta_rule_roofline.py``'s count)."""
    import jax
    import jax.numpy as jnp
    from znicz_tpu.ops import gdn
    t, h, dk, dv, chunk = 4096, 30, 96, 192, 64
    ks = jax.random.split(keys[6], 6)
    args = (gdn.l2_norm(jax.random.normal(ks[0], (1, t, h, dk))) * dk ** -0.5,
            gdn.l2_norm(jax.random.normal(ks[1], (1, t, h, dk))),
            jax.random.normal(ks[2], (1, t, h, dv)),
            -jnp.exp(jax.random.uniform(ks[3], (1, t, h), minval=-7.0,
                                        maxval=0.0)),
            jax.random.uniform(ks[4], (1, t, h), minval=0.05, maxval=1.95))
    do = jax.random.normal(ks[5], (1, t, h, dv))
    ops = 7.0 * t * h * dk * dv
    least = (max(ops / 197e12, 4.0 * t * h * (2 * dk + 2 * dv) / 819e9)
             + max(2 * ops / 197e12, 4.0 * t * h * (4 * dk + 3 * dv) / 819e9))
    _forms_compared("delta_rule", (gdn.delta_rule_chunked,
                                   gdn.delta_rule_kernel),
                    args, do, chunk, least)


def _forms_compared(name, forms, args, do, chunk, least) -> None:
    """The rule ``name``'s chunked form and its kernels (``forms``) at
    ``args``, ``chunk`` and bfloat16 operands: forward, and forward +
    backward (the gradient of all five inputs against ``do``), each form's
    share of the ``least`` time forward + backward, and the kernels' largest
    gaps to the chunked form's output and gradients."""
    import jax
    import jax.numpy as jnp

    def measured(form):
        def fwd(*a):
            return form(*a, chunk, jnp.bfloat16)
        grad = jax.grad(lambda *a: jnp.sum(fwd(*a) * do), tuple(range(5)))
        fwd, grad = jax.jit(fwd), jax.jit(grad)
        return (fwd(*args), grad(*args), 1e3 * timed(fwd, *args, reps=10),
                1e3 * timed(grad, *args, reps=10))
    want_o, want_g, t_f, t_b = measured(forms[0])
    print(json.dumps({"kernel": f"{name}_chunked", "fwd_ms": t_f,
                      "fwd_bwd_ms": t_b,
                      "roofline_share": least / (t_b * 1e-3)}), flush=True)
    o, g, t_f, t_b = measured(forms[1])
    gaps = [float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
            for a, b in zip((o, *g), (want_o, *want_g))]
    print(json.dumps({"kernel": f"{name}_kernel", "fwd_ms": t_f,
                      "fwd_bwd_ms": t_b,
                      "roofline_share": least / (t_b * 1e-3),
                      "gap_o_dq_dk_dv_dg_dbeta": gaps}), flush=True)


def kda_rule(keys) -> None:
    """One row of ``solar-open2-250b``'s KDA layer (4,096 tokens, 16 heads,
    keys and values of 128, chunks of 64, bfloat16 operands), as
    :func:`delta_rule` times the scalar rule; the share of the roofline is
    ``benchmark/metrics/solar_kda_rule_roofline.py``'s count."""
    import jax
    import jax.numpy as jnp
    from znicz_tpu.ops import gdn
    t, h, dk, dv, chunk = 4096, 16, 128, 128, 64
    ks = jax.random.split(keys[5], 6)
    # decays a channel of -exp(A_log) softplus(.) at the Mamba-2 starting
    # point: between about -1e-3 and -2 a token
    args = (gdn.l2_norm(jax.random.normal(ks[0], (1, t, h, dk))) * dk ** -0.5,
            gdn.l2_norm(jax.random.normal(ks[1], (1, t, h, dk))),
            jax.random.normal(ks[2], (1, t, h, dv)),
            -jnp.exp(jax.random.uniform(ks[3], (1, t, h, dk), minval=-7.0,
                                        maxval=0.7)),
            jax.random.uniform(ks[4], (1, t, h), minval=0.05, maxval=1.95))
    do = jax.random.normal(ks[5], (1, t, h, dv))
    ops = 7.0 * t * h * dk * dv
    least = (max(ops / 197e12, 4.0 * t * h * (3 * dk + 2 * dv) / 819e9)
             + max(2 * ops / 197e12, 4.0 * t * h * (6 * dk + 3 * dv) / 819e9))
    _forms_compared("kda_rule", (gdn.kda_rule_chunked, gdn.kda_rule_kernel),
                    args, do, chunk, least)


#: the decoder cells' tables and tokens a step: rows, width, tokens
EMBED_SHAPES = {"falcon-h1-34b-instruct": (32640, 5120, 4096),
                "olmo-hybrid-7b": (12544, 3840, 4096),
                "mellum2-12b-a2.5b": (24576, 2304, 8192),
                "granite-4.0-h-small": (12544, 4096, 2048)}


def embed(keys) -> None:
    """The embedding's table gradient alone at each decoder cell's shape,
    ids of its Markov rows (``benchmark/lib/decoder_model.py``): XLA's
    scatter-add (``jnp.take``'s transpose) against the kernel
    (``attention.embed_grad``), the largest gap of the two against the
    largest value, and the share of the HBM bound: the float32 table
    written once and the rows read once, over 819 GB/s."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.lib import decoder_model
    from znicz_tpu.ops import attention, segment
    for name, (rows, width, tokens) in EMBED_SHAPES.items():
        cfg = {"vocab_size": rows,
               "assumed": {"branching": 4, "chain_states": 1024}}
        ids, _ = decoder_model.make_rows(2147483647, np.arange(1), cfg,
                                         {"seq_len": tokens})
        ids = ids.reshape(-1)
        dy = jax.random.normal(keys[7], (tokens, width))
        table = jax.random.normal(keys[6], (rows, width))
        wd = jnp.zeros((), jnp.float32)
        scatter = jax.jit(lambda dy, w, wd: jnp.zeros(
            (rows, width), jnp.float32).at[ids].add(dy))
        # as the step has it: added in place into the update's decay term
        into_decay = jax.jit(lambda dy, w, wd: (wd * w).at[ids].add(dy))
        kernel = jax.jit(lambda dy, w, wd: attention.embed_grad(ids, dy,
                                                                rows))
        want, got = scatter(dy, table, wd), kernel(dy, table, wd)
        least = 4.0 * (rows + tokens) * width / 819e9
        for form, fn in (("scatter", scatter), ("scatter_into_decay",
                                                into_decay),
                         ("kernel", kernel)):
            t = timed(fn, dy, table, wd, reps=20)
            print(json.dumps({
                "kernel": "embed_grad", "config": name, "form": form,
                "rows": rows, "width": width, "tokens": tokens,
                "distinct_ids": int(jnp.unique(ids).size),
                "block_rows": segment.block_rows(rows, width),
                "ms": 1e3 * t, "hbm_bound_share": least / t,
                "gap": float(jnp.max(jnp.abs(got - want))
                             / jnp.max(jnp.abs(want)))}), flush=True)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
