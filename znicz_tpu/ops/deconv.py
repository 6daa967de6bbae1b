"""Transposed convolution (deconv): numpy golden + XLA tiers.

Parity target: the reference's ``deconv``/``gd_deconv`` kernels
(SURVEY.md §2.3 "deconv/depooling kernels" row) backing ``Deconv`` /
``GDDeconv`` — the autoencoder decoder path (SURVEY.md §2.2 [baseline
Deconv/GDDeconv]).

TPU-native design: deconv is the *adjoint* of conv, so every tier is
expressed through the conv-op adjoint pair already pinned by goldens in
``ops.conv`` rather than a new kernel family:

* forward    ``deconv(x, w)``      = conv-grad-input  (scatter / col2im)
* grad-input ``∂L/∂x``             = conv forward     (gather / im2col·W)
* grad-weights ``∂L/∂w``           = conv-grad-weights with the roles of
  "input" and "error" swapped (bilinearity of conv in (x, w)).

Weights keep the *paired conv's* HWIO layout ``(KH, KW, C_out, C_in)``
(``C_in`` = deconv input channels = the conv's ``n_kernels``), so weight
tying to an encoder Conv is a plain Vector share with no transpose.

Shape rule: the minimal consistent output extent
``H = stride·(OH−1) + K − 2·pad`` (the conv relation solved for its input
with zero remainder — matches the reference's ``compute_padding``-paired
geometry for every shipped sample)."""

from __future__ import annotations

import numpy as np

from . import conv as conv_ops
from .geometry import norm2 as _norm2


def deconv_out_size(size: int, k: int, stride: int, pad: int) -> int:
    """Minimal input extent whose conv output is ``size`` (zero remainder)."""
    return stride * (size - 1) + k - 2 * pad


def deconv_out_shape(x_shape, w_shape, stride=1, padding=0
                     ) -> tuple[int, int, int, int]:
    """NHWC output shape of deconv: x (B, OH, OW, C_in), w (KH, KW, C_out,
    C_in) → (B, H, W, C_out)."""
    b, oh, ow, cin = x_shape
    kh, kw, cout, cin_w = w_shape
    if cin != cin_w:
        raise ValueError(f"deconv channel mismatch: input has {cin}, "
                         f"weights expect {cin_w}")
    (sh, sw), (ph, pw) = _norm2(stride), _norm2(padding)
    return (b, deconv_out_size(oh, kh, sh, ph),
            deconv_out_size(ow, kw, sw, pw), cout)


# -- numpy golden tier -----------------------------------------------------
def np_deconv2d(x: np.ndarray, w: np.ndarray, stride=1, padding=0
                ) -> np.ndarray:
    """x: (B, OH, OW, C_in), w: (KH, KW, C_out, C_in) → (B, H, W, C_out)."""
    out_shape = deconv_out_shape(x.shape, w.shape, stride, padding)
    return conv_ops.np_conv2d_grad_input(x, w, out_shape, stride, padding)


def np_deconv2d_grad_input(err: np.ndarray, w: np.ndarray, stride=1,
                           padding=0) -> np.ndarray:
    """err: (B, H, W, C_out) → (B, OH, OW, C_in): the conv forward."""
    return conv_ops.np_conv2d(err, w, stride, padding)


def np_deconv2d_grad_weights(err: np.ndarray, x: np.ndarray,
                             w_shape, stride=1, padding=0) -> np.ndarray:
    """∂L/∂w with err (B, H, W, C_out) in the conv-input role and the
    deconv input x (B, OH, OW, C_in) in the conv-error role."""
    return conv_ops.np_conv2d_grad_weights(err, x, w_shape, stride, padding)


# -- XLA tier --------------------------------------------------------------
def xla_deconv2d(x, w, stride=1, padding=0, out_dtype=None):
    out_shape = deconv_out_shape(x.shape, w.shape, stride, padding)
    y = conv_ops.xla_conv2d_grad_input(x, w, out_shape, stride, padding)
    return y.astype(out_dtype or x.dtype)


def xla_deconv2d_grad_input(err, w, stride=1, padding=0):
    return conv_ops.xla_conv2d(err, w, stride, padding,
                               out_dtype=np.float32)


def xla_deconv2d_grad_weights(err, x, w_shape, stride=1, padding=0):
    return conv_ops.xla_conv2d_grad_weights(err, x, w_shape, stride,
                                            padding)


#: the one tier of each direction on an XLA device, under the names the
#: fused step and ``nn/`` call
deconv2d = xla_deconv2d
deconv2d_grad_input = xla_deconv2d_grad_input
deconv2d_grad_weights = xla_deconv2d_grad_weights
