"""The eager Tensor.

Reference analog: the pybind eager Tensor type
(/root/reference/paddle/fluid/pybind/eager.cc:1392) over phi::DenseTensor
(paddle/phi/core/dense_tensor.h:37). Here a Tensor is a thin mutable handle
over an immutable `jax.Array` plus autograd metadata (the AutogradMeta analog:
stop_gradient, grad, producing GradNode). Mutation (inplace ops, set_value,
optimizer updates) swaps the underlying array — the functional-array answer to
in-place CUDA kernels, and exactly what XLA wants (donation-friendly).

Most op methods (t.matmul, t.reshape, ...) are patched on by
paddle_tpu.ops.patch_tensor_methods at import time, mirroring the reference's
eager_math_op_patch.cc / tensor_patch_methods.py approach.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import autograd
from .dtype import convert_dtype, is_floating_point
from .place import Place, place_of, to_jax_device, get_default_place

# static Program capture flag — set by paddle_tpu.static.program_guard
# (mirrors dispatch._ProgramRecorder.active; lives here so the hot _value
# setter needs no cross-module import)
_prog_recording = [None]


def _to_array(data, dtype=None, place: Optional[Place] = None):
    if isinstance(data, Tensor):
        data = data._value
    dtype = convert_dtype(dtype)
    if isinstance(data, jax.Array):
        arr = data if dtype is None else data.astype(dtype)
    else:
        if isinstance(data, (bool, int, float, complex)) and dtype is None:
            # reference defaults: int -> int64 (physically int32, see
            # dtype._LOGICAL_64), float -> float32
            if isinstance(data, bool):
                dtype = np.dtype(np.bool_)
            elif isinstance(data, int):
                dtype = np.dtype(np.int32)
            elif isinstance(data, float):
                dtype = np.dtype(np.float32)
        npdata = np.asarray(data, dtype=dtype)
        if npdata.dtype == np.float64:
            npdata = npdata.astype(np.float32)
        elif npdata.dtype == np.int64:
            npdata = npdata.astype(np.int32)
        arr = jnp.asarray(npdata)
    if place is not None:
        dev = to_jax_device(place)
        if not isinstance(arr, jax.core.Tracer) and dev is not None:
            arr = jax.device_put(arr, dev)
    return arr


class Tensor:
    __slots__ = (
        "_value_raw",
        "stop_gradient",
        "grad",
        "_grad_node",
        "_out_index",
        "name",
        "persistable",
        "_hooks",
        "trainable",
        "_dist_attr",
        "_prog_uid",
        "__weakref__",
    )

    def __init__(
        self,
        data,
        dtype=None,
        place: Optional[Place] = None,
        stop_gradient: bool = True,
        name: Optional[str] = None,
        persistable: bool = False,
        _grad_node=None,
        _out_index: int = 0,
    ):
        self._value = _to_array(data, dtype, place)
        self.stop_gradient = stop_gradient
        self.grad: Optional[Tensor] = None
        self._grad_node = _grad_node
        self._out_index = _out_index
        self.name = name
        self.persistable = persistable
        self._hooks = []
        self.trainable = True

    # -- basic properties ---------------------------------------------------
    @property
    def _value(self):
        return self._value_raw

    @_value.setter
    def _value(self, v):
        # during static Program capture, rebinding a tensor's buffer is an
        # in-place mutation: freeze the pre-mutation value for already-
        # recorded consumers and drop the uid so later recorded ops see a
        # fresh SSA value (read live at replay)
        prog = _prog_recording[0]
        if prog is not None and \
                getattr(self, "_prog_uid", None) is not None:
            import warnings

            if isinstance(self, Parameter):
                # optimizer update captured mid-program: params keep their
                # LIVE binding (read fresh each run), but the computed
                # update is NOT written back at replay — static-mode
                # training belongs to jit.TrainStep / auto_parallel Engine
                warnings.warn(
                    "Parameter updated during static Program capture: "
                    "replay reads the live parameter each run but does "
                    "NOT apply captured optimizer updates — use "
                    "jit.TrainStep or the auto-parallel Engine for "
                    "training", RuntimeWarning, stacklevel=3)
            else:
                warnings.warn(
                    "in-place mutation of a captured tensor during "
                    "static Program recording: earlier ops keep the "
                    "pre-mutation value; later ops read the live value "
                    "at run time", RuntimeWarning, stacklevel=3)
                freeze = getattr(prog, "_freeze_external", None)
                if freeze is not None:
                    freeze(self)
                self._prog_uid = None
        self._value_raw = v

    @property
    def shape(self):
        return list(self._value.shape)

    @property
    def ndim(self):
        return self._value.ndim

    dim = ndim
    rank = ndim

    @property
    def dtype(self):
        return np.dtype(self._value.dtype)

    @property
    def size(self):
        return int(self._value.size)

    @property
    def place(self) -> Place:
        return place_of(self._value)

    @property
    def is_leaf(self):
        return self._grad_node is None

    @property
    def T(self):
        from .. import ops

        return ops.transpose(self, list(range(self.ndim))[::-1])

    @property
    def mT(self):
        from .. import ops

        perm = list(range(self.ndim))
        perm[-1], perm[-2] = perm[-2], perm[-1]
        return ops.transpose(self, perm)

    # -- conversion ---------------------------------------------------------
    def numpy(self):
        return np.asarray(jax.device_get(self._value))

    def item(self, *args):
        if args:
            return self.numpy().item(*args)
        return self.numpy().item()

    def tolist(self):
        return self.numpy().tolist()

    def astype(self, dtype):
        from .dispatch import apply

        d = convert_dtype(dtype)
        return apply(lambda x: x.astype(d), self, op_name="cast")

    cast = astype

    def to(self, *args, **kwargs):
        """to(dtype) / to(place) / to(device_str)."""
        out = self
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, Place):
                arr = jax.device_put(out._value, to_jax_device(a))
                out = Tensor(arr, stop_gradient=out.stop_gradient)
            elif isinstance(a, str) and a.split(":")[0] in (
                "cpu", "tpu", "gpu", "cuda",
            ):
                from .place import set_device, get_default_place
                name, _, idx = a.partition(":")
                p = Place("cpu" if name == "cpu" else "tpu",
                          int(idx) if idx else 0)
                arr = jax.device_put(out._value, to_jax_device(p))
                out = Tensor(arr, stop_gradient=out.stop_gradient)
            else:
                out = out.astype(a)
        return out

    def cpu(self):
        return self.to(Place("cpu", 0))

    def tpu(self, device_id=0):
        return self.to(Place("tpu", device_id))

    cuda = tpu  # reference-compat

    def pin_memory(self):
        return self

    # -- autograd -----------------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph=False):
        autograd.backward([self], [grad_tensor] if grad_tensor is not None
                          else None, retain_graph=retain_graph)

    def detach(self):
        t = Tensor(self._value, stop_gradient=True)
        t.name = self.name
        return t

    def detach_(self):
        self._grad_node = None
        self.stop_gradient = True
        return self

    def clone(self):
        from .dispatch import apply

        return apply(lambda x: x + 0, self, op_name="clone")

    def clear_grad(self, set_to_zero: bool = False):
        if set_to_zero and self.grad is not None:
            self.grad = Tensor(jnp.zeros_like(self.grad._value))
        else:
            self.grad = None

    clear_gradient = clear_grad

    def zero_grad(self):
        self.clear_grad()

    def register_hook(self, hook):
        self._hooks.append(hook)

        class _Handle:
            def remove(_self):
                try:
                    self._hooks.remove(hook)
                except ValueError:
                    pass

        return _Handle()

    def _accumulate_grad(self, cot):
        if self.grad is None:
            self.grad = Tensor(cot, stop_gradient=True)
        else:
            self.grad = Tensor(self.grad._value + cot, stop_gradient=True)

    # -- mutation -----------------------------------------------------------
    def set_value(self, value):
        """Replace the underlying buffer (shape/dtype-preserving assign)."""
        arr = _to_array(value)
        arr = arr.astype(self._value.dtype)
        if tuple(arr.shape) != tuple(self._value.shape):
            raise ValueError(
                f"set_value shape mismatch: {tuple(arr.shape)} vs "
                f"{tuple(self._value.shape)}"
            )
        self._value = arr
        return self

    def copy_(self, other, blocking=True):
        return self.set_value(other)

    def fill_(self, value):
        self._value = jnp.full_like(self._value, value)
        return self

    def zero_(self):
        return self.fill_(0)

    # -- indexing -----------------------------------------------------------
    def __getitem__(self, idx):
        from .dispatch import apply

        idx = _unwrap_index(idx)
        return apply(lambda x: x[idx], self, op_name="getitem")

    def __setitem__(self, idx, value):
        idx = _unwrap_index(idx)
        if not isinstance(value, Tensor):
            value = Tensor(value)
        # functional scatter-update; tape-visible as an op on (self, value).
        # GradNode captures self's CURRENT producer, so rebinding below is
        # safe (no self-loop) and grads flow to both old self and value.
        from .dispatch import apply

        out = apply(
            lambda x, val: x.at[idx].set(val.astype(x.dtype)),
            self,
            value,
            op_name="setitem",
        )
        self._value = out._value
        self._grad_node = out._grad_node
        self._out_index = out._out_index
        self.stop_gradient = out.stop_gradient

    # -- misc ---------------------------------------------------------------
    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __bool__(self):
        return bool(self.numpy())

    def __int__(self):
        return int(self.numpy())

    def __float__(self):
        return float(self.numpy())

    def __index__(self):
        return int(self.numpy())

    def __repr__(self):
        grad_info = "" if self.stop_gradient else ", stop_gradient=False"
        try:
            data = np.array2string(self.numpy(), precision=6, separator=", ")
        except Exception:
            data = f"<traced {self._value}>"
        return (
            f"Tensor(shape={self.shape}, dtype={self.dtype.name}, "
            f"place={self.place}{grad_info},\n       {data})"
        )

    def __format__(self, spec):
        if self.ndim == 0:
            return format(self.item(), spec)
        return repr(self)

    # numpy interop
    def __array__(self, dtype=None):
        arr = self.numpy()
        return arr.astype(dtype) if dtype is not None else arr

    # jax interop: let jnp.* accept Tensor directly
    def __jax_array__(self):
        return self._value

    @property
    def is_dist(self):
        return False

    def value(self):
        return self

    def get_tensor(self):
        return self


def _unwrap_index(idx):
    if isinstance(idx, Tensor):
        return idx._value
    if isinstance(idx, tuple):
        return tuple(_unwrap_index(i) for i in idx)
    if isinstance(idx, list):
        return [_unwrap_index(i) for i in idx]
    if isinstance(idx, slice):
        return slice(
            _unwrap_index(idx.start),
            _unwrap_index(idx.stop),
            _unwrap_index(idx.step),
        )
    return idx


class Parameter(Tensor):
    """A trainable Tensor (reference: paddle.base.framework.EagerParamBase)."""

    __slots__ = ("optimize_attr", "regularizer", "do_model_average",
                 "need_clip", "is_distributed", "split_axis")

    def __init__(self, data, dtype=None, name=None, trainable=True, **kw):
        super().__init__(
            data, dtype=dtype, name=name, stop_gradient=not trainable,
            persistable=True,
        )
        self.trainable = trainable
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.do_model_average = None
        self.need_clip = True
        self.is_distributed = False
        self.split_axis = None

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()
