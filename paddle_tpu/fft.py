"""paddle_tpu.fft (reference: python/paddle/fft.py) — jnp.fft backed."""
from __future__ import annotations

import jax.numpy as jnp

from .core.dispatch import apply

_F = jnp.fft

__all__ = ["fft", "ifft", "fft2", "ifft2", "fftn", "ifftn", "rfft", "irfft",
           "rfft2", "irfft2", "rfftn", "irfftn", "hfft", "ihfft", "fftfreq",
           "rfftfreq", "fftshift", "ifftshift"]


def _mk(name, fn, has_n=True):
    if has_n:
        def op(x, n=None, axis=-1, norm="backward", name=None):
            return apply(lambda a: fn(a, n=n, axis=int(axis), norm=norm), x,
                         op_name=name)
    else:
        def op(x, s=None, axes=None, norm="backward", name=None):
            return apply(lambda a: fn(a, s=s, axes=axes, norm=norm), x,
                         op_name=name)
    op.__name__ = name
    return op


fft = _mk("fft", _F.fft)
ifft = _mk("ifft", _F.ifft)
rfft = _mk("rfft", _F.rfft)
irfft = _mk("irfft", _F.irfft)
hfft = _mk("hfft", _F.hfft)
ihfft = _mk("ihfft", _F.ihfft)
fftn = _mk("fftn", _F.fftn, has_n=False)
ifftn = _mk("ifftn", _F.ifftn, has_n=False)
rfftn = _mk("rfftn", _F.rfftn, has_n=False)
irfftn = _mk("irfftn", _F.irfftn, has_n=False)


def fft2(x, s=None, axes=(-2, -1), norm="backward", name=None):
    return apply(lambda a: _F.fft2(a, s=s, axes=axes, norm=norm), x,
                 op_name="fft2")


def ifft2(x, s=None, axes=(-2, -1), norm="backward", name=None):
    return apply(lambda a: _F.ifft2(a, s=s, axes=axes, norm=norm), x,
                 op_name="ifft2")


def rfft2(x, s=None, axes=(-2, -1), norm="backward", name=None):
    return apply(lambda a: _F.rfft2(a, s=s, axes=axes, norm=norm), x,
                 op_name="rfft2")


def irfft2(x, s=None, axes=(-2, -1), norm="backward", name=None):
    return apply(lambda a: _F.irfft2(a, s=s, axes=axes, norm=norm), x,
                 op_name="irfft2")


def fftfreq(n, d=1.0, dtype=None, name=None):
    from .core.tensor import Tensor

    return Tensor(_F.fftfreq(int(n), d))


def rfftfreq(n, d=1.0, dtype=None, name=None):
    from .core.tensor import Tensor

    return Tensor(_F.rfftfreq(int(n), d))


def fftshift(x, axes=None, name=None):
    return apply(lambda a: _F.fftshift(a, axes=axes), x,
                 op_name="fftshift")


def ifftshift(x, axes=None, name=None):
    return apply(lambda a: _F.ifftshift(a, axes=axes), x,
                 op_name="ifftshift")


def _resolve_axes(ndim, s, axes):
    """numpy rule: axes default to the last len(s) axes (all axes when s
    is also None)."""
    if axes is not None:
        return list(axes)
    if s is not None:
        return list(range(ndim - len(s), ndim))
    return list(range(ndim))


def hfftn(x, s=None, axes=None, norm="backward", name=None):
    """N-D Hermitian FFT (reference fftn_c2r semantics): FORWARD fft over
    the leading axes, hfft over the last."""
    def fn(a):
        ax = _resolve_axes(a.ndim, s, axes)
        o = a
        for i, axis in enumerate(ax[:-1]):
            o = _F.fft(o, n=None if s is None else s[i], axis=axis,
                       norm=norm)
        return _F.hfft(o, n=None if s is None else s[-1],
                       axis=ax[-1], norm=norm)

    return apply(fn, x, op_name="hfftn")


def ihfftn(x, s=None, axes=None, norm="backward", name=None):
    """N-D inverse Hermitian FFT (reference fftn_r2c-conjugate semantics,
    ihfftn(x) == ifftn(x) truncated to the half spectrum): INVERSE fft
    over the leading axes, ihfft over the last."""
    def fn(a):
        ax = _resolve_axes(a.ndim, s, axes)
        o = _F.ihfft(a, n=None if s is None else s[-1], axis=ax[-1],
                     norm=norm)
        for i, axis in enumerate(ax[:-1]):
            o = _F.ifft(o, n=None if s is None else s[i], axis=axis,
                        norm=norm)
        return o

    return apply(fn, x, op_name="ihfftn")


def hfft2(x, s=None, axes=(-2, -1), norm="backward", name=None):
    return hfftn(x, s=s, axes=axes, norm=norm)


def ihfft2(x, s=None, axes=(-2, -1), norm="backward", name=None):
    return ihfftn(x, s=s, axes=axes, norm=norm)


__all__ += ["hfft2", "ihfft2", "hfftn", "ihfftn"]
