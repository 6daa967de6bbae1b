"""Train-time augmentation policies for the streaming loader family.

Parity target: the reference's ImageNet pipeline (SURVEY.md §2.2 "Znicz
loaders" row) — its on-the-fly loader served AlexNet with random crops
of a larger decoded frame plus horizontal mirroring at train time and a
deterministic center crop at eval [baseline: samples/AlexNet recipe].

TPU-first placement: augmentation runs on the host inside the decode
stage of the double-buffered prefetch (loader/streaming.py), so it
overlaps device compute like the rest of the host pipeline — the jitted
step keeps static shapes and no data-dependent gathers land on device.

Determinism: draws come from the framework counter RNG keyed
``(seed, epoch, global sample index)`` (ops/rngbits.py), so a sample's
crop window is a pure function of its coordinates — independent of
batch composition, prefetch order, or how many workers decoded it; the
unit-graph and fused streaming paths see identical pixels."""

from __future__ import annotations

import numpy as np

from ..ops import rngbits


class RandomCropFlip:
    """Random spatial crop + optional horizontal mirror (train rows);
    center crop, no mirror (eval rows and ``epoch=None``).

    Works on (B, H, W, ...) minibatches — channels-last like every image
    loader here; label/target blocks are untouched."""

    #: every output element is an input element, moved: a consumer that
    #: rounds the rows may round them before the augmentation instead
    #: (``FusedTrainer`` holds such a set in bfloat16)
    selects_only = True

    def __init__(self, out_hw: tuple[int, int], mirror: bool = True,
                 seed: int = 1234):
        self.out_hw = (int(out_hw[0]), int(out_hw[1]))
        self.mirror = bool(mirror)
        self.seed = int(seed)

    def out_shape(self, sample_shape: tuple) -> tuple:
        """Post-augmentation sample shape for a decoded frame shape."""
        if len(sample_shape) < 2:
            raise ValueError(f"RandomCropFlip needs (H, W, ...) samples,"
                             f" got {sample_shape}")
        h, w = self.out_hw
        if sample_shape[0] < h or sample_shape[1] < w:
            raise ValueError(f"crop {self.out_hw} exceeds decoded frame "
                             f"{sample_shape[:2]}")
        return (h, w, *sample_shape[2:])

    def device_apply(self, x, rows, epoch, train=True):
        """jnp twin of :meth:`apply` for the RESIDENT fused path: the
        same counter-RNG draws evaluated on device inside the jitted
        scan — crop windows BIT-IDENTICAL to the host pipeline's for
        the same (seed, epoch, global row), with no host round-trip
        (TPU-first: augmentation rides the scan, not the feed).

        ``train=False`` → deterministic center crop (the eval
        contract).  Assumes every row is a train row — the fused
        train_epoch serves train rows only."""
        import jax
        import jax.numpy as jnp

        from ..ops import rngbits

        big_h, big_w = int(x.shape[1]), int(x.shape[2])
        h, w = self.out_hw
        if (big_h, big_w) == (h, w) and not self.mirror:
            return x
        c_top, c_left = (big_h - h) // 2, (big_w - w) // 2
        if not train:
            return x[:, c_top:c_top + h, c_left:c_left + w]
        keys = rngbits.fold(self.seed, jnp.uint32(epoch),
                            rows.astype(jnp.uint32), xp=jnp)
        # (B, 3) lanes through the SAME public recipe the host path
        # draws with — one definition of the hash, two backends
        u = rngbits.uniform01(keys[:, None], 3, xp=jnp)
        tops = (u[:, 0] * (big_h - h + 1)).astype(jnp.int32)
        lefts = (u[:, 1] * (big_w - w + 1)).astype(jnp.int32)
        flips = (u[:, 2] >= 0.5) if self.mirror \
            else jnp.zeros((x.shape[0],), bool)

        def one(img, t, le, fl):
            win = jax.lax.dynamic_slice(
                img, (t, le) + (0,) * (img.ndim - 2),
                (h, w) + tuple(img.shape[2:]))
            return jnp.where(fl, win[:, ::-1], win)

        return jax.vmap(one)(x, tops, lefts, flips)

    def apply(self, data: np.ndarray, indices, epoch,
              is_train) -> np.ndarray:
        """Crop/flip a (B, H, W, ...) batch.

        ``is_train`` is a per-row bool mask (global-index split: eval
        rows get the center crop even inside a mixed batch)."""
        big_h, big_w = data.shape[1:3]
        h, w = self.out_hw
        if (big_h, big_w) == (h, w) and not self.mirror:
            return data            # crop is a no-op and no flips drawn
        out = np.empty((data.shape[0], h, w, *data.shape[3:]),
                       data.dtype)
        c_top, c_left = (big_h - h) // 2, (big_w - w) // 2
        idx = np.asarray(indices)
        for j in range(data.shape[0]):
            if epoch is not None and is_train[j]:
                key = rngbits.fold(self.seed, int(epoch), int(idx[j]))
                u = rngbits.uniform01(key, 3)
                top = int(u[0] * (big_h - h + 1))
                left = int(u[1] * (big_w - w + 1))
                flip = self.mirror and u[2] >= 0.5
            else:
                top, left, flip = c_top, c_left, False
            img = data[j, top:top + h, left:left + w]
            out[j] = img[:, ::-1] if flip else img
        return out
