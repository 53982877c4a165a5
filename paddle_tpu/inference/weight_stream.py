"""int8 double-buffered weight streaming for the paged serving decoder.

A one-token decode step reads every decoder weight once, so its HBM
traffic is dominated by the weights, not by the KV cache.  This module
cuts those bytes, the way the reference's weight-only-quant serving
kernels (paddle/phi/kernels/fusion — weight_only_linear) do on GPU (no
benchmark cell streams weights, so the gain is not measured):

1. **Per-channel int8 weights** — each decoder Linear stack weight
   (qkv / proj / gate_up / down) is stored as int8 with one f32 scale
   per output channel, halving (vs bf16) the bytes the decode step must
   stream, and dequantized on use.
2. **Double buffering** — layer i+1's dequant group is issued BEFORE
   layer i's compute (the same program-order prefetch shape as
   ``stage3_forward``'s FSDP gather prefetch), so XLA's latency-hiding
   scheduler overlaps the next layer's weight read + VPU dequant with
   matmuls it does not feed.  ``prefetch=False`` keeps dequant at the
   use site — the honest baseline ``measure_stream_win`` prices the
   overlap against, feeding ``weights/stream_prefetch_ms``.

Numerics: generations of a streaming engine are bitwise-identical to a
plain engine over the DEQUANTIZED weights (the quantization error vs
full precision is the usual weight-only-int8 tradeoff and is the
caller's call, exactly like ``cache_quant="int8"``).
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..profiler import metrics as _metrics

__all__ = ["STREAM_KINDS", "quantize_per_channel", "dequantize",
           "INT4_GROUP", "quantize_int4_grouped", "dequantize_int4",
           "WeightStreamer", "measure_stream_win"]

# the decoder Linear stacks streamed per layer (PagedCausalLM attribute
# names; biases do not exist in this architecture)
STREAM_KINDS = ("qkv", "proj", "gate_up", "down")

_m_prefetch = _metrics.histogram("weights/stream_prefetch_ms")


def quantize_per_channel(w) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric per-output-channel int8: ``w`` [in, out] float ->
    (int8 [in, out], f32 scale [out]) with w ~= q * scale."""
    a = np.asarray(jax.device_get(w), np.float32)
    amax = np.max(np.abs(a), axis=0)
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.round(a / scale), -127, 127).astype(np.int8)
    return q, scale


def dequantize(q, scale, dtype):
    """The exact in-trace dequant: int8 -> f32 multiply -> target dtype.
    Exposed so parity tests can reproduce the streamed weights bitwise."""
    return (jnp.asarray(q).astype(jnp.float32)
            * jnp.asarray(scale)).astype(dtype)


# int4 streaming: per-channel symmetric quant at 4 bits loses too much
# on the input dim, so scales are PER (input-group, output-channel) —
# each `INT4_GROUP`-row slab of a weight gets its own scale, bounding
# the quant error to the slab's dynamic range while still quartering
# (vs bf16) the bytes the decode step streams.
INT4_GROUP = 32


def quantize_int4_grouped(w, group: int = INT4_GROUP
                          ) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric int4 with per-(input-group, out-channel) scales:
    ``w`` [in, out] float -> (packed uint8 [in_pad//2, out],
    f32 scales [n_groups, out]) with w ~= q * scale, q in [-7, 7].
    Input rows pad to a multiple of ``group`` (zeros quantize to 0);
    two 4-bit codes (stored biased, q+8) pack per byte along the input
    axis — even row in the high nibble, odd row in the low."""
    a = np.asarray(jax.device_get(w), np.float32)
    d_in, d_out = a.shape
    n_g = -(-d_in // group)
    pad = n_g * group - d_in
    if pad:
        a = np.concatenate([a, np.zeros((pad, d_out), np.float32)])
    g = a.reshape(n_g, group, d_out)
    amax = np.max(np.abs(g), axis=1)                     # [n_g, out]
    scale = np.where(amax > 0, amax / 7.0, 1.0).astype(np.float32)
    q = np.clip(np.round(g / scale[:, None, :]), -7, 7)
    nib = (q.reshape(n_g * group, d_out) + 8).astype(np.uint8)
    packed = (nib[0::2] << 4) | nib[1::2]
    return packed, scale


def dequantize_int4(packed, scale, dtype, in_dim: int,
                    group: int = INT4_GROUP):
    """The exact in-trace int4 dequant: unpack nibbles, unbias, apply
    the per-group scale, drop the padding rows, cast.  Exposed so
    parity tests can reproduce the streamed weights bitwise."""
    p = jnp.asarray(packed)
    hi = (p >> 4) & 0xF
    lo = p & 0xF
    nib = jnp.stack([hi, lo], axis=1).reshape(-1, p.shape[1])
    q = nib.astype(jnp.float32) - 8.0
    s = jnp.repeat(jnp.asarray(scale), group, axis=0)
    return (q * s)[:in_dim].astype(dtype)


class WeightStreamer:
    """Per-layer int8 weight groups + the trace-time dequant schedule.

    Built ONCE at engine construction (``ServingEngine.from_model(...,
    weight_stream="int8")``): ``build`` pops the streamed weights out of
    the cast param tree (scalar placeholders keep the tree structure, so
    the bf16 copies are never staged to HBM) and quantizes them host-
    side.  At trace time ``bind`` rebinds the same schedule to the jit's
    traced arrays and ``PagedCausalLM.forward`` pulls per-layer groups
    through ``dequant_layer`` with the double-buffer loop."""

    def __init__(self, num_layers: int, dtype, prefetch: bool = True,
                 mode: str = "int8"):
        if mode not in ("int8", "int4"):
            raise ValueError("weight stream mode must be 'int8' or "
                             "'int4'")
        self.num_layers = int(num_layers)
        self.dtype = dtype
        self.prefetch = bool(prefetch)
        self.mode = mode
        self._q: Dict[Tuple[str, int], jnp.ndarray] = {}
        self._s: Dict[Tuple[str, int], jnp.ndarray] = {}
        # int4: original input dims (the packed array loses them to the
        # row padding) — host metadata, never traced
        self._in_dim: Dict[Tuple[str, int], int] = {}

    @classmethod
    def build(cls, model, params: Dict[str, object], dtype,
              prefetch: bool = True, mode: str = "int8"
              ) -> "WeightStreamer":
        """Quantize the decoder Linear stacks out of ``params`` (the
        name->array cast tree from ``current_params``), replacing each
        streamed leaf with a scalar placeholder."""
        ws = cls(model.cfg.num_layers, dtype, prefetch, mode)
        for kind in STREAM_KINDS:
            for li in range(ws.num_layers):
                name = f"{kind}.{li}.weight"
                if name not in params:
                    raise KeyError(
                        f"weight streaming expects '{name}' in the param "
                        f"tree (PagedCausalLM layout); have e.g. "
                        f"{sorted(params)[:4]}")
                if mode == "int4":
                    w = np.asarray(jax.device_get(params[name]))
                    ws._in_dim[(kind, li)] = int(w.shape[0])
                    q, s = quantize_int4_grouped(w)
                else:
                    q, s = quantize_per_channel(params[name])
                ws._q[(kind, li)] = jnp.asarray(q)
                ws._s[(kind, li)] = jnp.asarray(s)
                params[name] = jnp.zeros((), dtype)
        return ws

    def _ordered_keys(self) -> List[Tuple[str, int]]:
        return [(kind, li) for kind in STREAM_KINDS
                for li in range(self.num_layers)]

    def flat(self) -> List[jnp.ndarray]:
        """Streamed arrays in a stable order, appended to the engine's
        flat param list (and device_put with it)."""
        out = []
        for key in self._ordered_keys():
            out.append(self._q[key])
            out.append(self._s[key])
        return out

    def bind(self, flat) -> "WeightStreamer":
        """Rebind to the jit-traced copies of ``flat`` (same order)."""
        ws = WeightStreamer(self.num_layers, self.dtype, self.prefetch,
                            self.mode)
        ws._in_dim = dict(self._in_dim)
        it = iter(flat)
        for key in self._ordered_keys():
            ws._q[key] = next(it)
            ws._s[key] = next(it)
        return ws

    def dequant_layer(self, li: int) -> Dict[str, jnp.ndarray]:
        """Dequantize layer ``li``'s whole Linear group.  Where this call
        sits in program order IS the prefetch: issued one layer early
        under ``prefetch=True``, at the use site otherwise."""
        if self.mode == "int4":
            return {kind: dequantize_int4(self._q[(kind, li)],
                                          self._s[(kind, li)],
                                          self.dtype,
                                          self._in_dim[(kind, li)])
                    for kind in STREAM_KINDS}
        return {kind: dequantize(self._q[(kind, li)],
                                 self._s[(kind, li)], self.dtype)
                for kind in STREAM_KINDS}

    def quantized_bytes(self) -> int:
        return sum(int(a.size) * a.dtype.itemsize
                   for a in self.flat())


def measure_stream_win(stream_step, base_step, repeats: int = 3,
                       sync=None):
    """Price the double buffer: best-of wall times of two warmed decode
    step thunks (prefetched stream vs baseline), recording the per-call
    win into ``weights/stream_prefetch_ms``.  Returns
    ``(win_ms, t_stream_s, t_base_s)`` — the win is honest signed delta,
    negative when prefetch lost."""
    sync = sync or jax.block_until_ready

    def best(fn):
        dt = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            sync(fn())
            dt = min(dt, time.perf_counter() - t0)
        return dt

    sync(stream_step())                      # warm both executables
    sync(base_step())
    t_stream = best(stream_step)
    t_base = best(base_step)
    win_ms = (t_base - t_stream) * 1e3
    _m_prefetch.observe(max(win_ms, 0.0))
    return win_ms, t_stream, t_base
