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


# -- column-parity variants (rewrite (iii) of parallel/fused.py) -----------
# A conv whose output feeds a merged LRN+max-pool pair can emit the
# pair's column-parity halves DIRECTLY: the even/odd output columns of a
# stride-s conv are themselves convs with W-stride 2s and a ±s·p input
# offset (expressed as negative/asymmetric padding, which XLA supports).
# This removes the pair forward's split pass over the net's biggest
# activation, and the matching gradient decompositions let the pair
# backward hand its (dxe, dxo) halves straight to the conv grads — no
# interleave pass either.  All pure XLA; exactness pinned against the
# plain conv + split composition in tests.

def _parity_out_w(w: int, kw: int, sw: int, pw: int) -> tuple[int, int]:
    ow = out_size(w, kw, sw, pw)
    return -(-ow // 2), ow // 2          # even count, odd count


def xla_conv2d_split(x, w, stride=1, padding=0, out_dtype=None):
    """→ (y_even, y_odd): the column-parity halves of xla_conv2d."""
    kh, kw, _, oc = w.shape
    (sh, sw), (ph, pw) = _norm2(stride), _norm2(padding)
    _, h_in, w_in, _ = x.shape
    oh = out_size(h_in, kh, sh, ph)
    halves = []
    for p, target in zip((0, 1), _parity_out_w(w_in, kw, sw, pw)):
        if target == 0:
            # output width 1: the odd half is empty — mirror the
            # gradient twins' guard instead of building an impossible
            # negative-padding conv
            halves.append(jnp.zeros(
                (x.shape[0], oh, 0, oc), out_dtype or x.dtype))
            continue
        pl = pw - p * sw
        pr = (target - 1) * 2 * sw + kw - w_in - pl
        y = lax.conv_general_dilated(
            x, w, window_strides=(sh, 2 * sw),
            padding=((ph, ph), (pl, pr)), dimension_numbers=_DIMNUMS,
            preferred_element_type=jnp.float32)
        halves.append(y.astype(out_dtype or x.dtype))
    return halves[0], halves[1]


def xla_conv2d_grad_weights_split(x, err_e, err_o, w_shape, stride=1,
                                  padding=0):
    """Weight grad from parity-split output error halves — sums the two
    rhs-dilated convs (dilation 2·sw, input offset p·sw)."""
    kh, kw, c, oc = w_shape
    (sh, sw), (ph, pw) = _norm2(stride), _norm2(padding)
    dw = None
    for p, err in ((0, err_e), (1, err_o)):
        if err.shape[2] == 0:
            continue
        pl = pw - p * sw
        g = lax.conv_general_dilated(
            x, err, window_strides=(1, 1),
            padding=((ph, ph), (pl, pw + 2 * sw)),
            rhs_dilation=(sh, 2 * sw),
            dimension_numbers=lax.ConvDimensionNumbers(
                lhs_spec=(3, 0, 1, 2), rhs_spec=(3, 0, 1, 2),
                out_spec=(2, 3, 0, 1)),
            preferred_element_type=jnp.float32)[:kh, :kw]
        dw = g if dw is None else dw + g
    return dw.astype(jnp.float32)


def xla_conv2d_grad_input_split(err_e, err_o, w, x_shape, stride=1,
                                padding=0):
    """Input grad from parity-split output error halves — sums the two
    transposed convs (lhs_dilation 2·sw, offset-adjusted padding)."""
    kh, kw, c, oc = w.shape
    (sh, sw), (ph, pw) = _norm2(stride), _norm2(padding)
    _, h, w_in, _ = x_shape
    w_flip = jnp.transpose(w[::-1, ::-1, :, :], (0, 1, 3, 2))
    dx = None
    for p, err in ((0, err_e), (1, err_o)):
        ow_p = err.shape[2]
        if ow_p == 0:
            continue
        _, oh, _, _ = err.shape
        lo_h = kh - 1 - ph
        hi_h = h + ph - ((oh - 1) * sh + 1) - (kh - 1) + kh - 1
        lo_w = kw - 1 - (pw - p * sw)
        hi_w = w_in - 1 + kw - lo_w - ((ow_p - 1) * 2 * sw + 1)
        g = lax.conv_general_dilated(
            err, w_flip, window_strides=(1, 1),
            padding=((lo_h, hi_h), (lo_w, hi_w)),
            lhs_dilation=(sh, 2 * sw), dimension_numbers=_DIMNUMS,
            preferred_element_type=jnp.float32)
        dx = g if dx is None else dx + g
    return dx.astype(jnp.float32)


#: the one tier of each direction on an XLA device, under the names the
#: fused step and ``nn/`` call
conv2d = xla_conv2d
conv2d_grad_input = xla_conv2d_grad_input
conv2d_grad_weights = xla_conv2d_grad_weights
