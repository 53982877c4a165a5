"""Pallas TPU kernels — the fused-op layer.

Reference analog: paddle/phi/kernels/fusion/gpu/ (hand-written CUDA fused
kernels: flash attention, fused_rms_norm, fused_rope, ...). On TPU, XLA
already fuses elementwise chains into matmuls, so only the ops XLA fuses
poorly get hand kernels: attention (online-softmax blockwise over the KV
axis) and rmsnorm-style HBM-bound reductions. Every kernel has a pure-jnp
fallback (used on CPU test meshes and as the custom_vjp backward).
"""
import os

import jax

from ...core.place import on_tpu
from ...profiler import metrics as _metrics


def use_pallas() -> bool:
    """Kernels are on when the default backend is a TPU; PT_USE_PALLAS=0/1
    overrides. A backend that cannot start raises here — it does not
    quietly turn the kernels off."""
    flag = os.environ.get("PT_USE_PALLAS", "auto")
    if flag in ("0", "false", "off"):
        return False
    if flag in ("1", "true", "on"):
        return True
    return on_tpu()


def interpret() -> bool:
    """PT_PALLAS_INTERPRET=1 runs the Pallas kernels in interpreter mode on
    any backend — CI coverage for the kernel code paths on the CPU suite."""
    return os.environ.get("PT_PALLAS_INTERPRET", "0") == "1"


def kernels_enabled() -> bool:
    return use_pallas() or interpret()


def per_shard(fn, mesh, in_specs, out_specs):
    """`fn`, a function that calls a Pallas kernel, made safe inside a jit
    over `mesh`. The SPMD partitioner cannot partition a Mosaic kernel
    ("Mosaic kernels cannot be automatically partitioned"): on a mesh of
    several devices the call has to sit in a fully manual shard_map, each
    device running the kernel on its own shard. `in_specs`/`out_specs`
    say how the operands are laid out; they must leave whole the axes the
    kernel reduces over. With no mesh or one device `fn` is returned
    unchanged. Called inside a shard_map that is already manual over some
    of the mesh's axes (the pipeline ring is, over 'pp'), the region nests:
    it takes the enclosing region's mesh and is manual over the rest."""
    if mesh is None or mesh.size == 1:
        return fn
    outer = set(jax.sharding.get_abstract_mesh().manual_axes)
    return jax.shard_map(fn, mesh=None if outer else mesh,
                         in_specs=in_specs, out_specs=out_specs,
                         axis_names=set(mesh.axis_names) - outer,
                         check_vma=False)


def note_reference_dispatch(kernel: str) -> None:
    """Count one trace-time decision to run `kernel`'s jnp reference
    although kernels are enabled (the shape does not tile). The dispatch
    stays; the counter makes it visible — chip_smoke.py asserts it stays
    at zero along its path."""
    _metrics.inc("pallas/reference_dispatch")
    _metrics.inc("pallas/reference_dispatch/" + kernel)
