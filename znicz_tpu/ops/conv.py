"""2-D convolution: numpy golden and XLA tiers.

Parity target: the reference's ``conv.cl``/``conv.cu`` + gradient variants
(SURVEY.md §2.3 row 2: block-tiled, unpack-in-kernel im2col forward and the
correlate/weight-grad backward kernels feeding ``Conv``/``GDConv``).

TPU-native design decisions:

* **Layout is NHWC / HWIO** — channels on the 128-lane minor dimension,
  which is what the TPU vector unit and XLA's conv emitter want.  (The
  reference used flattened row-major sample buffers; NCHW-era layouts pay
  a layout change on TPU.)
* **XLA tier** uses ``lax.conv_general_dilated`` — XLA lowers convs
  straight onto the MXU with its own implicit im2col, fused with adjacent
  elementwise ops; this is the production path.
* **Hand-written gradients** (the reference's GDConv contract) are pinned
  by the numpy goldens below via explicit im2col/col2im; the XLA gradient
  tier expresses the same math as dilated/transposed convolutions.  Tests
  cross-check numpy vs XLA vs ``jax.grad``.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp
from jax import lax

from .geometry import norm2 as _norm2, out_size

_DIMNUMS = ("NHWC", "HWIO", "NHWC")


# -- numpy golden tier -----------------------------------------------------
def np_im2col(x: np.ndarray, kx: tuple[int, int], stride: tuple[int, int],
              pad: tuple[int, int]) -> np.ndarray:
    """(B, OH, OW, KH*KW*C) patches; zero padding."""
    b, h, w, c = x.shape
    (kh, kw), (sh, sw), (ph, pw) = kx, stride, pad
    xp = np.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    oh, ow = out_size(h, kh, sh, ph), out_size(w, kw, sw, pw)
    s = xp.strides
    shape = (b, oh, ow, kh, kw, c)
    strides = (s[0], s[1] * sh, s[2] * sw, s[1], s[2], s[3])
    cols = np.lib.stride_tricks.as_strided(xp, shape, strides)
    return np.ascontiguousarray(cols).reshape(b, oh, ow, kh * kw * c)


def np_conv2d(x: np.ndarray, w: np.ndarray, stride=1, padding=0
              ) -> np.ndarray:
    """x: (B,H,W,C), w: (KH,KW,C,OC) → (B,OH,OW,OC)."""
    kh, kw, c, oc = w.shape
    stride, padding = _norm2(stride), _norm2(padding)
    cols = np_im2col(x, (kh, kw), stride, padding)
    b, oh, ow, _ = cols.shape
    y = cols.reshape(-1, kh * kw * c) @ w.reshape(-1, oc)
    return y.reshape(b, oh, ow, oc)


def np_conv2d_grad_weights(x: np.ndarray, err: np.ndarray,
                           w_shape: tuple[int, ...], stride=1, padding=0
                           ) -> np.ndarray:
    """∇w[kh,kw,ci,co] = Σ_{b,oh,ow} x_patch · err (im2colᵀ · err)."""
    kh, kw, c, oc = w_shape
    stride, padding = _norm2(stride), _norm2(padding)
    cols = np_im2col(x, (kh, kw), stride, padding)
    g = cols.reshape(-1, kh * kw * c).T @ err.reshape(-1, oc)
    return g.reshape(w_shape)


def np_conv2d_grad_input(err: np.ndarray, w: np.ndarray,
                         x_shape: tuple[int, ...], stride=1, padding=0
                         ) -> np.ndarray:
    """col2im scatter of err · wᵀ back onto the (padded) input."""
    kh, kw, c, oc = w.shape
    (sh, sw), (ph, pw) = _norm2(stride), _norm2(padding)
    b, h, w_in, _ = x_shape
    _, oh, ow, _ = err.shape
    cols = err.reshape(-1, oc) @ w.reshape(-1, oc).T   # (B*OH*OW, KH*KW*C)
    cols = cols.reshape(b, oh, ow, kh, kw, c)
    dx = np.zeros((b, h + 2 * ph, w_in + 2 * pw, c), np.float32)
    for i in range(kh):
        for j in range(kw):
            dx[:, i:i + oh * sh:sh, j:j + ow * sw:sw, :] += cols[:, :, :,
                                                                 i, j, :]
    return dx[:, ph:ph + h, pw:pw + w_in, :]


# -- XLA tier --------------------------------------------------------------
def xla_conv2d(x, w, stride=1, padding=0, out_dtype=None):
    (sh, sw), (ph, pw) = _norm2(stride), _norm2(padding)
    y = lax.conv_general_dilated(
        x, w, window_strides=(sh, sw), padding=((ph, ph), (pw, pw)),
        dimension_numbers=_DIMNUMS,
        preferred_element_type=jnp.float32)
    return y.astype(out_dtype or x.dtype)


def xla_conv2d_grad_input(err, w, x_shape, stride=1, padding=0):
    """Hand-written transposed conv: dilate err by stride, correlate with
    the spatially-flipped, IO-swapped kernel."""
    kh, kw, c, oc = w.shape
    (sh, sw), (ph, pw) = _norm2(stride), _norm2(padding)
    _, h, w_in, _ = x_shape
    _, oh, ow, _ = err.shape
    w_flip = jnp.transpose(w[::-1, ::-1, :, :], (0, 1, 3, 2))  # (KH,KW,OC,C)
    lo_h, lo_w = kh - 1 - ph, kw - 1 - pw
    hi_h = h + ph - ((oh - 1) * sh + 1) - (kh - 1) + kh - 1
    hi_w = w_in + pw - ((ow - 1) * sw + 1) - (kw - 1) + kw - 1
    dx = lax.conv_general_dilated(
        err, w_flip, window_strides=(1, 1),
        padding=((lo_h, hi_h), (lo_w, hi_w)), lhs_dilation=(sh, sw),
        dimension_numbers=_DIMNUMS,
        preferred_element_type=jnp.float32)
    return dx.astype(jnp.float32)


def xla_conv2d_grad_weights(x, err, w_shape, stride=1, padding=0):
    """Hand-written weight grad: a conv contracting over the batch —
    x's batch acts as the input-feature dim, err acts as an rhs-dilated
    kernel whose "spatial" extent is (OH, OW)."""
    kh, kw, c, oc = w_shape
    (sh, sw), (ph, pw) = _norm2(stride), _norm2(padding)
    dw = lax.conv_general_dilated(
        x, err, window_strides=(1, 1), padding=((ph, ph), (pw, pw)),
        rhs_dilation=(sh, sw),
        dimension_numbers=lax.ConvDimensionNumbers(
            lhs_spec=(3, 0, 1, 2),   # x (B,H,W,C): batch=C, feature=B
            rhs_spec=(3, 0, 1, 2),   # err (B,OH,OW,OC): out=OC, in=B
            out_spec=(2, 3, 0, 1)),  # result laid out (KH, KW, C, OC)
        preferred_element_type=jnp.float32)
    # input extents that aren't an exact multiple of the stride leave
    # extra taps past the true kernel support — trim them
    return dw[:kh, :kw].astype(jnp.float32)


#: the one tier of each direction on an XLA device, under the names the
#: fused step and ``nn/`` call
conv2d = xla_conv2d
conv2d_grad_input = xla_conv2d_grad_input
conv2d_grad_weights = xla_conv2d_grad_weights
