"""The parallel hybrid block: attention and a Mamba-2 mixer side by side,
both reading one normed input, their outputs summed into the stream.

``parallel_block`` is, for the heads one chip holds,

    n  = RMSNorm(x; g1)
    x' = x + attn_scale * Attn(n) + ssm_scale * Mamba2(n)

with ``Attn`` the attention mixer of ``ops/attention.py``
(:func:`~.attention.attn_mixer`) and ``Mamba2`` the one of ``ops/ssm.py``
(:func:`~.ssm.mamba_mixer`), each under its own config: ``cfg["attn"]`` and
``cfg["ssm"]`` are the sorted items of the mixers' configs (the keys
``nn/decoder.py``'s ``attn_mixer_config`` and ``ssm_mixer_config`` make).  A
model's multiplier on the keys rides on ``attn``'s ``score_scale``: it meets
nothing but the scores.  The backward is ``jax.vjp`` of this function under
the trainer's block rematerialisation, as for every other sequence kind."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .attention import attn_mixer, rms_norm
from .ssm import mamba_mixer

#: leaves of the block, in the order of ``parallel_block_fwd``'s ``leaves``:
#: the norm's gain, the attention mixer's four, the Mamba mixer's eight
ATTN_LEAVES = ("wq", "wk", "wv", "wo")
SSM_LEAVES = ("w_in", "conv_w", "conv_b", "dt_bias", "a_log", "d_skip",
              "g_m", "w_out")
LEAVES = ("g1", *ATTN_LEAVES, *SSM_LEAVES)


def parallel_block_fwd(leaves, x, cfg: dict, cdt=jnp.float32):
    """``parallel_block``: leaves as :data:`LEAVES`; ``cfg``: ``eps``,
    ``attn``, ``ssm``, ``attn_scale`` and ``ssm_scale`` (None: 1).  ->
    ``(y, counters)``; ``parallel_tokens`` counts the tokens this layer ran
    through both mixers."""
    g1, *rest = leaves
    attn_leaves, ssm_leaves = rest[:len(ATTN_LEAVES)], rest[len(ATTN_LEAVES):]
    if len(ssm_leaves) != len(SSM_LEAVES):
        raise ValueError(f"parallel_block with {len(leaves)} leaves: it "
                         f"takes {len(LEAVES)}")
    eps = cfg["eps"]
    bsz, t, _ = x.shape

    def scaled(out, key):
        return out if cfg.get(key) is None else out * cfg[key]
    with jax.named_scope("parallel_block"):
        n = rms_norm(x, g1, eps)
        with jax.named_scope("attn_mixer"):
            attn = attn_mixer(attn_leaves, n, {**dict(cfg["attn"]),
                                               "eps": eps}, cdt)
        with jax.named_scope("mamba_mixer"):
            ssm = mamba_mixer(ssm_leaves, n, {**dict(cfg["ssm"]),
                                              "eps": eps}, cdt)
        out = scaled(attn, "attn_scale") + scaled(ssm, "ssm_scale")
    return x + out, {"parallel_tokens": jnp.asarray(bsz * t, jnp.int32)}
